"""Separable resampling as matmuls — the ladder scaler.

Replaces ffmpeg's ``scale=w:h:flags=lanczos`` filter (reference:
worker/hwaccel.py:672-704 inserts one scale filter per quality rung, and
transcoder.py:2528-2559 runs the rungs as parallel ffmpeg processes). On TPU
a resample along one axis is a small dense matrix multiply, so a full frame
resize is ``A_h @ img @ A_w.T`` — two MXU matmuls — and the *whole ladder*
shares one decoded source resident in HBM.

Filter matrices are built host-side with numpy (cached per
(src, dst, filter)), normalized rows, and handle both down- and up-scaling
(kernel scaled by the downsampling ratio, matching swscale/Pillow
semantics).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _lanczos(x: np.ndarray, a: int = 3) -> np.ndarray:
    x = np.abs(x)
    out = np.where(x < 1e-8, 1.0, np.sinc(x) * np.sinc(x / a))
    return np.where(x >= a, 0.0, out)


def _triangle(x: np.ndarray) -> np.ndarray:
    x = np.abs(x)
    return np.maximum(0.0, 1.0 - x)


def _box(x: np.ndarray) -> np.ndarray:
    return np.where(np.abs(x) <= 0.5, 1.0, 0.0)


_FILTERS = {
    "lanczos3": (_lanczos, 3.0),
    "bilinear": (_triangle, 1.0),
    "box": (_box, 0.5),
}


@functools.lru_cache(maxsize=256)
def resample_matrix(src: int, dst: int, filter: str = "lanczos3") -> np.ndarray:
    """Dense (dst, src) resampling matrix with normalized rows.

    Sample positions use the center convention: source pixel i sits at
    i + 0.5. For downscales the kernel support is widened by src/dst
    (anti-aliasing), as in swscale and PIL.
    """
    try:
        kernel, support = _FILTERS[filter]
    except KeyError:
        raise ValueError(f"unknown resize filter {filter!r}") from None
    scale = src / dst
    width = support * max(scale, 1.0)
    # Center of dst pixel j in source coordinates.
    centers = (np.arange(dst) + 0.5) * scale  # (dst,)
    positions = np.arange(src) + 0.5  # (src,)
    x = (positions[None, :] - centers[:, None]) / max(scale, 1.0)
    w = kernel(x)
    w[np.abs(positions[None, :] - centers[:, None]) > width + 1e-9] = 0.0
    # Clamp-to-edge: fold weight that falls outside the image back onto the
    # edge samples by renormalizing rows.
    rowsum = w.sum(axis=1, keepdims=True)
    rowsum[rowsum == 0.0] = 1.0
    return (w / rowsum).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("dst_h", "dst_w", "filter", "out_dtype"))
def resize_plane(plane, dst_h: int, dst_w: int, *, filter: str = "lanczos3", out_dtype=jnp.uint8):
    """Resize a (..., H, W) plane to (..., dst_h, dst_w).

    Two matmuls: rows then columns. uint8 input is promoted to f32; output
    is rounded/clipped back to ``out_dtype`` (pass jnp.float32 to keep
    precision for chained ops).
    """
    src_h, src_w = plane.shape[-2], plane.shape[-1]
    a_h = jnp.asarray(resample_matrix(src_h, dst_h, filter))
    a_w = jnp.asarray(resample_matrix(src_w, dst_w, filter))
    return apply_resize_matrices(plane, a_h, a_w, out_dtype)


def resize_yuv420(y, u, v, dst_h: int, dst_w: int, *, filter: str = "lanczos3"):
    """Resize a planar 4:2:0 frame batch; dst_h/dst_w must be even.

    Identity resizes are skipped (the top rung of a ladder usually equals
    the source size — no work, and no giant identity matrix baked into
    the program).
    """
    if dst_h % 2 or dst_w % 2:
        raise ValueError("4:2:0 target dimensions must be even")
    if (y.shape[-2], y.shape[-1]) == (dst_h, dst_w):
        if y.dtype != jnp.uint8:   # keep the uint8 output contract
            return (jnp.clip(jnp.round(y), 0, 255).astype(jnp.uint8),
                    jnp.clip(jnp.round(u), 0, 255).astype(jnp.uint8),
                    jnp.clip(jnp.round(v), 0, 255).astype(jnp.uint8))
        return y, u, v
    return (
        resize_plane(y, dst_h, dst_w, filter=filter),
        resize_plane(u, dst_h // 2, dst_w // 2, filter=filter),
        resize_plane(v, dst_h // 2, dst_w // 2, filter=filter),
    )


# --------------------------------------------------------------------------
# Matrices-as-arguments variant.
#
# Inside a jit trace, `resample_matrix` constants are baked into the HLO;
# for big ladders (4K sources) that bloats the program past what remote
# compile services accept and duplicates data per-compile. These helpers
# thread the matrices through as runtime arguments instead: build them
# once host-side with `plan_ladder_matrices`, pass the pytree to the
# traced function, apply with `resize_yuv420_with`.
# --------------------------------------------------------------------------

def plan_ladder_matrices(src_h: int, src_w: int,
                         rungs_hw: tuple[tuple[int, int], ...],
                         filter: str = "lanczos3") -> dict:
    """{(h, w): ((A_h, A_w), (A_h_c, A_w_c)) | None} for every rung.

    None marks an identity (source-size) rung. Chroma matrices are the
    half-resolution pair. Memoized per (geometry, rungs, filter) — every
    program (re)build used to pay the full lanczos window construction
    again; callers get a fresh dict each call (safe to mutate) backed by
    the cached immutable plan.
    """
    return dict(_plan_ladder_cached(src_h, src_w, tuple(rungs_hw), filter))


@functools.lru_cache(maxsize=64)
def _plan_ladder_cached(src_h: int, src_w: int,
                        rungs_hw: tuple[tuple[int, int], ...],
                        filter: str) -> tuple:
    if src_h % 2 or src_w % 2:
        raise ValueError("4:2:0 source dimensions must be even")
    mats = []
    for (h, w) in rungs_hw:
        if h % 2 or w % 2:
            raise ValueError(f"4:2:0 rung dimensions must be even: {(h, w)}")
        if (h, w) == (src_h, src_w):
            mats.append(((h, w), None))
            continue
        mats.append(((h, w), (
            (resample_matrix(src_h, h, filter), resample_matrix(src_w, w, filter)),
            (resample_matrix(src_h // 2, h // 2, filter),
             resample_matrix(src_w // 2, w // 2, filter)),
        )))
    return tuple(mats)


def apply_resize_matrices(plane, a_h, a_w, out_dtype=jnp.uint8):
    """(..., H, W) x (h, H) x (w, W) -> (..., h, w). Pure/traced."""
    x = plane.astype(jnp.float32)
    x = jnp.einsum("hH,...Hw->...hw", a_h, x, precision=jax.lax.Precision.HIGHEST)
    x = jnp.einsum("...hw,Ww->...hW", x, a_w, precision=jax.lax.Precision.HIGHEST)
    if out_dtype == jnp.uint8:
        return jnp.clip(jnp.round(x), 0, 255).astype(jnp.uint8)
    return x.astype(out_dtype)


@jax.named_scope("ladder.resize")
def resize_yuv420_with(y, u, v, rung_mats):
    """Resize with prebuilt matrices (None = identity rung)."""
    if rung_mats is None:
        # Same clamp/cast contract as the matrix path: float inputs must
        # not flow unclamped into the encode.
        def _to_u8(p):
            if p.dtype == jnp.uint8:
                return p
            return jnp.clip(jnp.round(p.astype(jnp.float32)), 0, 255).astype(jnp.uint8)
        return _to_u8(y), _to_u8(u), _to_u8(v)
    (a_h, a_w), (c_h, c_w) = rung_mats
    return (
        apply_resize_matrices(y, a_h, a_w),
        apply_resize_matrices(u, c_h, c_w),
        apply_resize_matrices(v, c_h, c_w),
    )


def ladder_resize_yuv420(y, u, v, rungs, *, filter: str = "lanczos3"):
    """One decoded source -> every quality rung, in one traced program.

    ``rungs`` is a static tuple of (height, width). Returns a dict
    {(h, w): (Y, U, V)}. This is the "one pass emits all rungs" core of the
    TPU ladder (reference needed one ffmpeg process per rung,
    transcoder.py:2528-2559); XLA keeps the source in HBM and fuses the
    per-rung matmul pairs.
    """
    out = {}
    for (h, w) in rungs:
        out[(h, w)] = resize_yuv420(y, u, v, h, w, filter=filter)
    return out
