"""What the plain references share: sinusoidal positions and LayerNorm,
as the published descriptions define them."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def position_encoding(length: int, d_model: int) -> np.ndarray:
    pos = np.arange(length)[:, None].astype(np.float64)
    i = np.arange(d_model // 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, 2 * i / d_model)
    enc = np.zeros((length, d_model))
    enc[:, 0::2] = np.sin(angle)
    enc[:, 1::2] = np.cos(angle)
    return enc.astype(np.float32)


def layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias
