"""Shared bits proxy for device-side in-chain rate adaptation.

One definition serves both codec paths (parallel/ladder.py for H.264,
codecs/hevc/jax_core.py for HEVC): the host calibrates ONE bytes-per-
proxy-unit scalar per rung from realized chain bytes, so the device
cost and that calibration must always use the same formula — nnz +
sum log2(1+|l|), the shape of entropy-coded coefficient cost for both
CAVLC/CABAC families.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("ladder.bitproxy")
def cost_proxy(*level_arrays, batch_ndim: int = 0):
    """Bits proxy over level tensors: nnz + sum log2(1+|l|).

    Reduces every axis except the leading ``batch_ndim`` axes; returns
    a float32 scalar (batch_ndim=0) or (batch...,) array.
    """
    tot = 0.0
    for a in level_arrays:
        af = jnp.abs(a.astype(jnp.float32))
        axes = tuple(range(batch_ndim, a.ndim))
        tot = tot + jnp.sum((af > 0) + jnp.log2(1.0 + af), axis=axes)
    return tot
