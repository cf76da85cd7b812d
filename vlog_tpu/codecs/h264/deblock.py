"""H.264 in-loop deblocking filter (spec 8.7) — exact, TPU-shaped.

The reference gets deblocking for free inside x264/NVENC
(worker/hwaccel.py:647); our encoder must implement it in the JAX DSP
because the filter is IN-LOOP: the deblocked picture is what a decoder
uses as the P-frame reference, so the encoder's reconstruction must be
bit-exact with spec order or prediction drifts.

**Why a wavefront.** Spec 8.7 processes macroblocks in raster order;
within an MB, the four vertical edges left-to-right, then the four
horizontal edges top-to-bottom — each filter reading the latest
partially-filtered samples. Writes of one edge overlap reads of its
neighbours (a vertical MB-boundary filter reads the 4 columns its left
neighbour's horizontal filters just wrote), so the exact computation has
a wavefront dependency structure: MB (r, c) needs (r, c-1), (r-1, c) and
(r-1, c+1). We schedule op ``idx`` (0-3 vertical, 4-7 horizontal) of MB
(r, c) at phase ``8*(r + c) + idx``: every phase runs ONE op type over a
whole anti-diagonal of MBs — ``lax.scan`` over ``mbh + mbw - 1``
diagonals with an unrolled 8-op body, each op a batched gather/filter/
scatter over the diagonal (and over the GOP batch dimension when
vmapped). Exactness is by construction: phase order is a linear
extension of the spec's read/write partial order (row skew 8 covers the
worst cross-row dependency, H(r,c,0) after V(r-1,c+1,0)).

Boundary strengths for the streams this encoder emits:

- I frames (Intra_16x16): MB-boundary edges bS=4 (strong filter),
  internal edges bS=3.
- P frames (P_L0_16x16, one MV per MB): bS=2 where either adjacent 4x4
  luma block has nonzero coefficients, else bS=1 across MB boundaries
  where the MV delta is >= 4 quarter-pel on either component, else 0
  (spec 8.7.2.1 for the P_16x16 / single-ref case).

alpha/beta/tc0 are spec Tables 8-16/8-17 (values cross-checked against
libavcodec's h264_loopfilter tables). QP is uniform per frame here
(per-frame rate control), so threshold lookups are traced scalars.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from vlog_tpu.codecs.h264.encoder import chroma_qp

# Spec Table 8-16 (alpha, beta as functions of indexA/indexB 0..51).
ALPHA = np.array([
    0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 4, 4, 5, 6, 7, 8,
    9, 10, 12, 13, 15, 17, 20, 22, 25, 28, 32, 36, 40, 45, 50, 56, 63,
    71, 80, 90, 101, 113, 127, 144, 162, 182, 203, 226, 255, 255,
], np.int32)
BETA = np.array([
    0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 2, 2, 2, 3, 3, 3,
    3, 4, 4, 4, 6, 6, 7, 7, 8, 8, 9, 9, 10, 10, 11, 11, 12, 12, 13,
    13, 14, 14, 15, 15, 16, 16, 17, 17, 18, 18,
], np.int32)
# Spec Table 8-17: tc0 by (bS-1, indexA). Row 0 is bS=1.
TC0 = np.array([
    [0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
     0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 4, 4, 4, 5,
     6, 6, 7, 8, 9, 10, 11, 13],
    [0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 1,
     1, 1, 1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 4, 4, 5, 5, 6, 7,
     8, 8, 10, 11, 12, 13, 15, 17],
    [0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 1,
     1, 1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 4, 4, 4, 5, 6, 6, 7, 8, 9,
     10, 11, 13, 14, 16, 18, 20, 23, 25],
], np.int32)


# ---------------------------------------------------------------------------
# Boundary strengths
# ---------------------------------------------------------------------------

def intra_bs(mbh: int, mbw: int):
    """(bs_v, bs_h) for an all-Intra_16x16 frame, each (mbh, mbw, 4, 4):
    [r, c, edge_idx, segment] — MB-boundary edges 4, internal 3.
    Picture-boundary edges are masked off in the scan, values unused."""
    bs = np.full((mbh, mbw, 4, 4), 3, np.int32)
    bs[:, :, 0, :] = 4
    return jnp.asarray(bs), jnp.asarray(bs)


@jax.named_scope("ladder.deblock")
def p_bs(nz4, mv):
    """Boundary strengths for a P frame.

    nz4: (4*mbh, 4*mbw) bool/int — 4x4 luma block has nonzero levels.
    mv: (mbh, mbw, 2) int32 quarter-pel MVs (one per MB).
    Returns (bs_v, bs_h), each (mbh, mbw, 4, 4) int32 [r, c, edge, seg].
    """
    nz4 = nz4.astype(jnp.int32)
    mbh, mbw = mv.shape[0], mv.shape[1]
    # nz per edge: either side's 4x4 block coded -> bS 2
    nzl = jnp.pad(nz4, ((0, 0), (1, 0)))[:, :-1]        # left neighbour
    nzu = jnp.pad(nz4, ((1, 0), (0, 0)))[:-1, :]        # upper neighbour
    pair_v = ((nz4 | nzl) > 0)                          # (4mbh, 4mbw)
    pair_h = ((nz4 | nzu) > 0)
    # MV-difference >= 4 qpel applies only across MB boundaries (one MV
    # per MB here, internal edges have zero delta by construction)
    dv = jnp.abs(mv - jnp.pad(mv, ((0, 0), (1, 0), (0, 0)))[:, :-1])
    dh = jnp.abs(mv - jnp.pad(mv, ((1, 0), (0, 0), (0, 0)))[:-1, :])
    mv_v = jnp.any(dv >= 4, axis=-1)                    # (mbh, mbw)
    mv_h = jnp.any(dh >= 4, axis=-1)

    def shape(p, mvd):
        # p[r, c, i, s] — edge index i, segment s — already arranged by
        # the caller; MV bS=1 applies only to MB-boundary edges (i == 0)
        bs = jnp.where(p, 2, 0)
        mvterm = jnp.where(mvd[:, :, None, None], 1, 0)
        edge0 = jnp.maximum(bs[:, :, 0:1, :], mvterm)
        return jnp.concatenate([edge0, bs[:, :, 1:, :]], axis=2)

    # vertical edge i at x=16c+4i, segment s along y (block row 4r+s):
    # pair_v[4r+s, 4c+i] -> [r, c, i, s]
    pv = pair_v.reshape(mbh, 4, mbw, 4).transpose(0, 2, 3, 1)
    # horizontal edge i at y=16r+4i, segment s along x (block col 4c+s):
    # pair_h[4r+i, 4c+s] -> [r, c, i, s]
    ph = pair_h.reshape(mbh, 4, mbw, 4).transpose(0, 2, 1, 3)
    return shape(pv, mv_v), shape(ph, mv_h)


# ---------------------------------------------------------------------------
# Line filters: win (..., 8) = [p3 p2 p1 p0 q0 q1 q2 q3] along the line
# ---------------------------------------------------------------------------

def _filter_luma_lines(win, bs, alpha, beta, tc0_row):
    """Spec 8.7.2.2 (normal, bS 1..3) + 8.7.2.3 (strong, bS 4).

    win: (..., 8) int32; bs: (...,) int32 per line; tc0_row: (3,) traced
    tc0 values for bS 1..3 at the frame QP. Returns the filtered window.
    """
    p3, p2, p1, p0 = win[..., 0], win[..., 1], win[..., 2], win[..., 3]
    q0, q1, q2, q3 = win[..., 4], win[..., 5], win[..., 6], win[..., 7]
    filt = ((bs > 0)
            & (jnp.abs(p0 - q0) < alpha)
            & (jnp.abs(p1 - p0) < beta)
            & (jnp.abs(q1 - q0) < beta))
    ap = jnp.abs(p2 - p0) < beta
    aq = jnp.abs(q2 - q0) < beta

    # ---- normal filter (bS 1..3)
    tc0 = tc0_row[jnp.clip(bs, 1, 3) - 1]
    tc = tc0 + ap.astype(jnp.int32) + aq.astype(jnp.int32)
    delta = jnp.clip(((q0 - p0) * 4 + (p1 - q1) + 4) >> 3, -tc, tc)
    p0n = jnp.clip(p0 + delta, 0, 255)
    q0n = jnp.clip(q0 - delta, 0, 255)
    p1n = p1 + jnp.clip((p2 + ((p0 + q0 + 1) >> 1) - 2 * p1) >> 1,
                        -tc0, tc0)
    q1n = q1 + jnp.clip((q2 + ((p0 + q0 + 1) >> 1) - 2 * q1) >> 1,
                        -tc0, tc0)
    p1n = jnp.where(ap, p1n, p1)
    q1n = jnp.where(aq, q1n, q1)

    # ---- strong filter (bS 4)
    strong_p = ap & (jnp.abs(p0 - q0) < ((alpha >> 2) + 2))
    strong_q = aq & (jnp.abs(p0 - q0) < ((alpha >> 2) + 2))
    p0s = jnp.where(strong_p,
                    (p2 + 2 * p1 + 2 * p0 + 2 * q0 + q1 + 4) >> 3,
                    (2 * p1 + p0 + q1 + 2) >> 2)
    p1s = jnp.where(strong_p, (p2 + p1 + p0 + q0 + 2) >> 2, p1)
    p2s = jnp.where(strong_p,
                    (2 * p3 + 3 * p2 + p1 + p0 + q0 + 4) >> 3, p2)
    q0s = jnp.where(strong_q,
                    (q2 + 2 * q1 + 2 * q0 + 2 * p0 + p1 + 4) >> 3,
                    (2 * q1 + q0 + p1 + 2) >> 2)
    q1s = jnp.where(strong_q, (q2 + q1 + q0 + p0 + 2) >> 2, q1)
    q2s = jnp.where(strong_q,
                    (2 * q3 + 3 * q2 + q1 + q0 + p0 + 4) >> 3, q2)

    is4 = bs == 4
    p2o = jnp.where(filt & is4, p2s, p2)
    p1o = jnp.where(filt, jnp.where(is4, p1s, p1n), p1)
    p0o = jnp.where(filt, jnp.where(is4, p0s, p0n), p0)
    q0o = jnp.where(filt, jnp.where(is4, q0s, q0n), q0)
    q1o = jnp.where(filt, jnp.where(is4, q1s, q1n), q1)
    q2o = jnp.where(filt & is4, q2s, q2)
    return jnp.stack([p3, p2o, p1o, p0o, q0o, q1o, q2o, q3], axis=-1)


def _filter_chroma_lines(win, bs, alpha, beta, tc0_row):
    """Chroma edge filter: win (..., 4) = [p1 p0 q0 q1]."""
    p1, p0, q0, q1 = win[..., 0], win[..., 1], win[..., 2], win[..., 3]
    filt = ((bs > 0)
            & (jnp.abs(p0 - q0) < alpha)
            & (jnp.abs(p1 - p0) < beta)
            & (jnp.abs(q1 - q0) < beta))
    # normal: tc = tc0 + 1 (spec: chroma always adds 1)
    tc = tc0_row[jnp.clip(bs, 1, 3) - 1] + 1
    delta = jnp.clip(((q0 - p0) * 4 + (p1 - q1) + 4) >> 3, -tc, tc)
    p0n = jnp.clip(p0 + delta, 0, 255)
    q0n = jnp.clip(q0 - delta, 0, 255)
    # strong (bS 4)
    p0s = (2 * p1 + p0 + q1 + 2) >> 2
    q0s = (2 * q1 + q0 + p1 + 2) >> 2
    is4 = bs == 4
    p0o = jnp.where(filt, jnp.where(is4, p0s, p0n), p0)
    q0o = jnp.where(filt, jnp.where(is4, q0s, q0n), q0)
    return jnp.stack([p1, p0o, q0o, q1], axis=-1)


# ---------------------------------------------------------------------------
# Wavefront frame filter
# ---------------------------------------------------------------------------

def _edge_pass_v(plane, r_idx, x0, seg_bs, mask, alpha, beta, tc0_row,
                 *, mb, wwin, chroma):
    """Filter the vertical edges at per-row columns ``x0`` (one edge per
    active diagonal row). plane (H, W); r_idx (n,) MB rows; x0 (n,)
    edge columns; seg_bs (n, 4) per-segment bS; mask (n,) active."""
    h, w = plane.shape
    half = wwin // 2
    rows = r_idx[:, None] * mb + jnp.arange(mb)[None, :]        # (n, mb)
    cols = jnp.clip(x0[:, None] - half + jnp.arange(wwin)[None, :],
                    0, w - 1)                                    # (n, wwin)
    win = plane[rows[:, :, None], cols[:, None, :]]              # (n,mb,wwin)
    # per-line bS: segment s covers lines 4s..4s+3 (luma) / 2s.. (chroma)
    lines_per_seg = mb // 4
    bs_l = jnp.repeat(seg_bs, lines_per_seg, axis=1)             # (n, mb)
    f = _filter_chroma_lines if chroma else _filter_luma_lines
    out = f(win, bs_l, alpha, beta, tc0_row)
    out = jnp.where(mask[:, None, None], out, win)
    return plane.at[rows[:, :, None], cols[:, None, :]].set(out)


def _edge_pass_h(plane, r_idx, c_idx, y0, seg_bs, mask, alpha, beta,
                 tc0_row, *, mb, wwin, chroma):
    """Horizontal edges: transpose roles (lines run along x)."""
    h, w = plane.shape
    half = wwin // 2
    rows = jnp.clip(y0[:, None] - half + jnp.arange(wwin)[None, :],
                    0, h - 1)                                    # (n, wwin)
    cols = c_idx[:, None] * mb + jnp.arange(mb)[None, :]         # (n, mb)
    win = plane[rows[:, :, None], cols[:, None, :]]              # (n,wwin,mb)
    win = jnp.swapaxes(win, 1, 2)                                # (n,mb,wwin)
    lines_per_seg = mb // 4
    bs_l = jnp.repeat(seg_bs, lines_per_seg, axis=1)
    f = _filter_chroma_lines if chroma else _filter_luma_lines
    out = f(win, bs_l, alpha, beta, tc0_row)
    out = jnp.where(mask[:, None, None], out, win)
    out = jnp.swapaxes(out, 1, 2)                                # (n,wwin,mb)
    return plane.at[rows[:, :, None], cols[:, None, :]].set(out)


@partial(jax.jit, static_argnames=("mbh", "mbw"))
def _deblock_wavefront(y, u, v, qp, bs_v, bs_h, *, mbh, mbw):
    ia = jnp.clip(qp, 0, 51)
    alpha = jnp.asarray(ALPHA)[ia]
    beta = jnp.asarray(BETA)[ia]
    tc0_row = jnp.asarray(TC0)[:, ia]                            # (3,)
    qpc = chroma_qp(qp)
    alpha_c = jnp.asarray(ALPHA)[jnp.clip(qpc, 0, 51)]
    beta_c = jnp.asarray(BETA)[jnp.clip(qpc, 0, 51)]
    tc0_c = jnp.asarray(TC0)[:, jnp.clip(qpc, 0, 51)]

    r_idx = jnp.arange(mbh)

    def diag(carry, k):
        yy, uu, vv = carry
        c_idx = k - r_idx                                        # (mbh,)
        valid = (c_idx >= 0) & (c_idx < mbw)
        c_cl = jnp.clip(c_idx, 0, mbw - 1)
        segs_v = bs_v[r_idx, c_cl]                               # (mbh, 4, 4)
        segs_h = bs_h[r_idx, c_cl]
        for i in range(4):                       # vertical edges, x order
            x0 = c_cl * 16 + 4 * i
            m = valid & ((c_idx > 0) | (i > 0))  # picture-left edge off
            yy = _edge_pass_v(yy, r_idx, x0, segs_v[:, i], m,
                              alpha, beta, tc0_row,
                              mb=16, wwin=8, chroma=False)
            if i % 2 == 0:                       # chroma edges at x/2
                cseg = segs_v[:, i]              # luma bS, chroma lines
                xc = c_cl * 8 + 2 * i
                uu = _edge_pass_v(uu, r_idx, xc, cseg, m, alpha_c,
                                  beta_c, tc0_c, mb=8, wwin=4,
                                  chroma=True)
                vv = _edge_pass_v(vv, r_idx, xc, cseg, m, alpha_c,
                                  beta_c, tc0_c, mb=8, wwin=4,
                                  chroma=True)
        for j in range(4):                       # horizontal edges, y order
            y0 = r_idx * 16 + 4 * j
            m = valid & ((r_idx > 0) | (j > 0))  # picture-top edge off
            yy = _edge_pass_h(yy, r_idx, c_cl, y0, segs_h[:, j], m,
                              alpha, beta, tc0_row,
                              mb=16, wwin=8, chroma=False)
            if j % 2 == 0:
                yc = r_idx * 8 + 2 * j
                uu = _edge_pass_h(uu, r_idx, c_cl, yc, segs_h[:, j], m,
                                  alpha_c, beta_c, tc0_c, mb=8,
                                  wwin=4, chroma=True)
                vv = _edge_pass_h(vv, r_idx, c_cl, yc, segs_h[:, j], m,
                                  alpha_c, beta_c, tc0_c, mb=8,
                                  wwin=4, chroma=True)
        return (yy, uu, vv), None

    (y, u, v), _ = jax.lax.scan(
        diag, (y, u, v), jnp.arange(mbh + mbw - 1))
    return y, u, v


@jax.named_scope("ladder.deblock")
def deblock_frame(y, u, v, *, qp, bs_v, bs_h):
    """Deblock one reconstructed frame in place of spec 8.7.

    y (H, W), u/v (H/2, W/2) integer planes (uint8 ok); ``qp`` traced or
    Python int; bS arrays from :func:`intra_bs` / :func:`p_bs`. Returns
    filtered (y, u, v) as int32 (callers cast/clip as needed — values
    stay in [0, 255] by construction).
    """
    h, w = y.shape
    mbh, mbw = h // 16, w // 16
    return _deblock_wavefront(
        y.astype(jnp.int32), u.astype(jnp.int32), v.astype(jnp.int32),
        jnp.asarray(qp, jnp.int32), bs_v, bs_h, mbh=mbh, mbw=mbw)
