"""Weights from ``--seed`` for a model too large for ``weights.py``
(which draws every matrix in float32 in ONE jitted call: 13.2 GB for the
3.3 B parameters of ``solar_open2_250b_ep8_d4``): one jitted draw per
matrix (``paddle_tpu.ops.basic.hash_normal``, elementwise from the
element's index: no bit buffer), in the matrix's own dtype, each
replacing its variable before the next is drawn — so the device holds
the model once, plus one matrix. The seed is a run-time argument and the drawers are cached by
shape, dtype and std: no seed compiles anything, and matrices of one
shape share one program.

As with ``weights.py`` the program's own start-up runs first, with its
fixed seed: it lays out every variable (gains, the decay's A_log and
dt_bias, pools, recurrent state) and its matrices are then replaced.
"""

from __future__ import annotations

import functools

import numpy as np


def matrix_spec(params: dict, std_of) -> tuple:
    """((name, shape, dtype, std), ...) of the parameters of rank >= 2,
    sorted by name; ``params`` maps name -> (shape, dtype) and
    ``std_of(name, shape)`` is the model's own rule."""
    return tuple(
        (name, tuple(int(d) for d in shape), str(dtype),
         float(std_of(name, shape)))
        for name, (shape, dtype) in sorted(params.items())
        if len(shape) >= 2)


@functools.lru_cache(maxsize=None)
def _drawer(shape: tuple, dtype: str, std: float):
    import jax
    from paddle_tpu.ops.basic import hash_normal
    # the program's own elementwise draw (its start-up's initializer),
    # with the seed as a run-time argument: no bit buffer beside the
    # matrix, whatever its size
    return jax.jit(lambda seed, index: hash_normal(shape, dtype, std,
                                                   seed, index))


def reseed(scope, spec: tuple, seed: int, device) -> None:
    """Replace each matrix of ``spec`` in ``scope`` by the draw of
    ``seed`` (matrix i salted with i), committed to ``device`` like
    every long-lived array."""
    import jax
    seed_arr = jax.device_put(np.uint32(seed % (2 ** 32)), device)
    for i, (name, shape, dtype, std) in enumerate(spec):
        index = jax.device_put(np.uint32(i), device)
        scope.set_var(name, _drawer(shape, dtype, std)(seed_arr, index))
