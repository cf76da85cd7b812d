"""Fused Pallas ladder rung: resize + quantize + uint8 in ONE kernel.

The XLA path (ops/resize.py `apply_resize_matrices`) lowers each rung to
three dispatches — the H-axis resample matmul, the W-axis resample
matmul, and the round/clip/uint8 quantize — with the intermediate f32
plane making a full HBM round-trip between each. This module is the
north-star "one-pass ladder kernel" (SNIPPETS.md [1]): a single
``pallas_call`` per plane streams the uint8 source through VMEM once,
applies BOTH resample matrices and the YUV plane quantize in-core, and
writes uint8 back — one HBM read of the source and one HBM write of the
rung per plane.

Tiling: grid ``(batch, H-blocks)``. Each cell stages one full source
plane (uint8) plus its output-row block of ``A_h`` and the whole ``A_w``
in VMEM and emits a ``(block_rows, dst_w)`` strip of the rung. Block
rows divide ``dst_h`` exactly, so no masked edges exist and the kernel
body can be the *verbatim* op sequence of ``apply_resize_matrices``
(f32 cast -> two HIGHEST-precision einsums -> clip/round/uint8) — that
is what makes the Pallas output BYTE-IDENTICAL to the XLA path, which
tier-1 asserts across the full shape x depth matrix in interpret mode.

Where it runs (PR 21, first contact with Mosaic on a TPU v5 lite,
jax 0.9.0):

- On CPU the kernel runs with ``interpret=True``; that is the vehicle of
  the byte-identity tests and nothing else.
- On a TPU Mosaic REFUSES this kernel as written: ``Unsupported cast:
  uint8 -> float32`` for every shape, and for the 360p chroma plane the
  ``(90, 360)`` block of ``A_h`` is not (8, 128)-tiled. A variant with
  32-row blocks, lane-padded ``A_w`` and the casts routed through int32
  does lower, but matched the XLA bytes at one block size and differed
  by one LSB at another — byte identity there is a property of the MXU
  accumulation order, not of the op sequence. So ``auto`` resolves to
  the XLA path on every platform, and ``VLOG_PALLAS=1`` on a TPU raises
  Mosaic's own error at the first trace instead of degrading. There is
  no probe and no per-rung fallback: a plane is fused because it was
  asked for, or it is not fused. Re-tile or delete: ROADMAP Speed 5.

This is the ONLY module allowed to touch ``jax.experimental.pallas``
(analysis/pallasshim.py enforces containment); program builders select
the plane via :func:`ladder_resize` / the ``VLOG_PALLAS`` knob.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from vlog_tpu.ops.resize import resize_yuv420_with


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _block_rows(dst_h: int) -> int:
    """Largest divisor of ``dst_h`` <= 128 (exact blocks: no masked
    edge rows, which keeps the kernel body identical to the XLA ops)."""
    best = 1
    d = 1
    while d * d <= dst_h:
        if dst_h % d == 0:
            for cand in (d, dst_h // d):
                if cand <= 128 and cand > best:
                    best = cand
        d += 1
    return best


def _rung_kernel(src_ref, ah_ref, aw_ref, out_ref):
    # VERBATIM op sequence of ops/resize.py apply_resize_matrices on a
    # (1, H, W) block — the byte-identity contract with the XLA path.
    x = src_ref[...].astype(jnp.float32)
    x = jnp.einsum("hH,...Hw->...hw", ah_ref[...], x,
                   precision=jax.lax.Precision.HIGHEST)
    x = jnp.einsum("...hw,Ww->...hW", x, aw_ref[...],
                   precision=jax.lax.Precision.HIGHEST)
    out_ref[...] = jnp.clip(jnp.round(x), 0, 255).astype(jnp.uint8)


def fused_resize_plane(plane, a_h, a_w):
    """(..., H, W) x (h, H) x (w, W) -> (..., h, w) uint8, one HBM pass.

    Never falls back: off-TPU the kernel is interpreted, on a TPU a
    lowering error from Mosaic propagates (see the module docstring).
    """
    src_h, src_w = plane.shape[-2], plane.shape[-1]
    dst_h, dst_w = a_h.shape[0], a_w.shape[0]
    bh = _block_rows(dst_h)
    lead = plane.shape[:-2]
    x = plane.reshape((-1, src_h, src_w))
    n = x.shape[0]
    out = pl.pallas_call(
        _rung_kernel,
        grid=(n, dst_h // bh),
        in_specs=[
            pl.BlockSpec((1, src_h, src_w), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((bh, src_h), lambda i, j: (j, 0)),
            pl.BlockSpec((dst_w, src_w), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bh, dst_w), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, dst_h, dst_w), jnp.uint8),
        interpret=_interpret(),
    )(x, a_h, a_w)
    return out.reshape(lead + (dst_h, dst_w))


@jax.named_scope("ladder.resize")
def resize_yuv420_pallas(y, u, v, rung_mats):
    """Drop-in for ops/resize.py ``resize_yuv420_with`` on the fused
    plane. Identity rungs (mats None) share the XLA path's clamp/cast
    contract — there is no resample to fuse."""
    if rung_mats is None:
        return resize_yuv420_with(y, u, v, None)
    (a_h, a_w), (c_h, c_w) = rung_mats
    return (
        fused_resize_plane(y, a_h, a_w),
        fused_resize_plane(u, c_h, c_w),
        fused_resize_plane(v, c_h, c_w),
    )


def use_pallas(mode: str | None = None) -> bool:
    """Resolve VLOG_PALLAS (auto|1|0) to the plane this process runs.

    ``auto`` and ``0`` are the XLA path on every platform; ``1`` is the
    fused kernel wherever it is asked for — interpreted off-TPU (the
    byte-identity test vehicle), compiled by Mosaic on a TPU, where the
    kernel as written is refused and the refusal raises.
    """
    if mode is None:
        from vlog_tpu import config

        mode = config.PALLAS
    return str(mode).strip().lower() in ("1", "on", "true")


def ladder_resize(pallas: bool) -> Callable:
    """The resize plane a program builder compiles against."""
    return resize_yuv420_pallas if pallas else resize_yuv420_with
