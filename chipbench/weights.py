"""Weights from ``--seed``, made on the device in one jitted call.

The program's own start-up bakes its seed into the compiled program
(``core/lowering.py``: ``jax.random.key(program.random_seed)`` is a
constant of the trace), so a seed per run would compile a start-up
program per run. The runners therefore run start-up with one fixed
seed — it lays out every variable: LayerNorm gains, biases, position
tables, optimizer moments, KV pools — and then replace every weight
MATRIX by a draw from ``--seed`` here, where the seed is a run-time
argument and one compiled program serves every seed.
"""

from __future__ import annotations

import functools

import numpy as np


def matrix_spec(params: dict, d_model: int) -> tuple:
    """((name, shape, std), ...) for the parameters of rank >= 2, sorted
    by name: embeddings [V, d_model] get the program's d_model**-0.5,
    other matrices the Glorot std sqrt(2 / (fan_in + fan_out))."""
    spec = []
    for name in sorted(params):
        shape = tuple(int(d) for d in params[name])
        if len(shape) < 2:
            continue
        embedding = shape[0] > 4 * shape[1] and shape[1] == d_model
        std = d_model ** -0.5 if embedding \
            else (2.0 / (shape[0] + shape[1])) ** 0.5
        spec.append((name, shape, float(std)))
    return tuple(spec)


@functools.lru_cache(maxsize=None)
def _drawer(shapes_stds: tuple):
    import jax
    import jax.numpy as jnp

    def draw(seed):
        keys = jax.random.split(jax.random.key(seed), len(shapes_stds))
        return [jax.random.normal(k, shape, jnp.float32) * std
                for k, (shape, std) in zip(keys, shapes_stds)]
    return jax.jit(draw)


def reseed(scope, spec: tuple, seed: int, device) -> None:
    """Replace the matrices named in ``spec`` in ``scope`` by the draw of
    ``seed`` (committed to ``device``, like every long-lived array)."""
    import jax
    seed_arr = jax.device_put(np.uint32(seed % (2 ** 32)), device)
    values = _drawer(tuple((s, std) for _n, s, std in spec))(seed_arr)
    for (name, _shape, _std), value in zip(spec, values):
        scope.set_var(name, value)
