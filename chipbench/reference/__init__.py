"""Plain references: each configuration's forward pass (for training,
its loss and gradients too) in straightforward jax.numpy, float32, matmul
precision "highest": no kernels, no cache, no batching tricks."""
