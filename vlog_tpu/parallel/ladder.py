"""Sharded one-pass ladder: the multi-chip version of the hot loop.

``shard_map`` over the mesh's "data" axis: every device holds a shard of
the GOP's frames and produces quantized H.264 levels for EVERY rung of
its local frames — resize + transform + quantize fused into one XLA
program per device, zero collectives in steady state (all-intra frames
are independent; the only cross-device traffic is the initial scatter and
final gather over ICI).

Resize matrices are threaded as runtime arguments (replicated across the
mesh), not trace-time constants — at 4K the ladder's dense matrices are
~100MB, which must live in HBM once, not inside the serialized program
(ops/resize.py `plan_ladder_matrices`).

This is the step __graft_entry__.dryrun_multichip exercises and the
unit the v5e-8 worker dispatches per frame batch (SURVEY.md section 2d
item 5: DP across chips over frame batches).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vlog_tpu.parallel.mesh import RungGrid, shard_frames, shard_map

from vlog_tpu.codecs.h264.encoder import encode_frame
from vlog_tpu.ops.pallas_ladder import ladder_resize, use_pallas
from vlog_tpu.ops.resize import plan_ladder_matrices, resize_yuv420_with

# Static description of one rung: (name, height, width, qp)
RungSpec = tuple[str, int, int, int]


def _jit_frames(fn, mesh):
    """jit with frame-tensor buffer donation where it is safe+useful.

    The y/u/v args (argnums 0-2) are per-dispatch ``shard_frames``
    device arrays the GridProgram drops right after the call, so on TPU
    their HBM pages can back the outputs instead of doubling the
    working set. Donation stays off when mesh is None (single-chip
    dispatch feeds host numpy — nothing donatable) and off-TPU
    (XLA:CPU donation is a no-op that warns per dispatch).
    """
    import jax as _jax

    if mesh is not None and _jax.devices()[0].platform == "tpu":
        return jax.jit(fn, donate_argnums=(0, 1, 2))
    return jax.jit(fn)


def _pad_mb(y, u, v):
    """Edge-pad a (n, H, W) YUV420 batch to macroblock alignment (traced;
    SPS cropping restores display size downstream)."""
    h, w = y.shape[-2], y.shape[-1]
    ph, pw = (-h) % 16, (-w) % 16
    if ph or pw:
        y = jnp.pad(y, ((0, 0), (0, ph), (0, pw)), mode="edge")
        u = jnp.pad(u, ((0, 0), (0, ph // 2), (0, pw // 2)), mode="edge")
        v = jnp.pad(v, ((0, 0), (0, ph // 2), (0, pw // 2)), mode="edge")
    return y, u, v


def ladder_matrices(rungs: tuple[RungSpec, ...], src_h: int, src_w: int) -> dict:
    """{rung name: resize-matrix pytree (or None for identity)}."""
    by_hw = plan_ladder_matrices(src_h, src_w, tuple((h, w) for _, h, w, _ in rungs))
    return {name: by_hw[(h, w)] for name, h, w, _ in rungs}


def _encode_rung(y, u, v, rung_mats, qp, resize=resize_yuv420_with):
    """Shared per-rung body: resize -> MB-pad -> batch intra encode.

    ``qp`` is a scalar or a (n,) per-frame vector (traced — rate control
    steps QP without recompiling). Returns (levels, resized_y) —
    resized_y is the display-size luma used for quality stats.
    ``resize`` is the resize plane the program was built for (the XLA
    einsum path, or ops/pallas_ladder's fused kernel — byte-identical).
    """
    ry, ru, rv = resize(y, u, v, rung_mats)
    py, pu, pv = _pad_mb(ry, ru, rv)
    qv = jnp.broadcast_to(jnp.asarray(qp, jnp.int32), (py.shape[0],))
    with jax.named_scope("ladder.intra"):
        levels = jax.vmap(
            lambda a, b, c, q: encode_frame(a, b, c, qp=q))(py, pu, pv, qv)
    return levels, ry


def ladder_local(y, u, v, mats: dict, rungs: tuple[RungSpec, ...], qps=None,
                 resize=resize_yuv420_with):
    """Device-local body: frames (n, H, W) -> levels for every rung.

    ``qps`` optionally maps rung name -> per-frame QP vector; rungs'
    static QP is the default.
    """
    return {name: _encode_rung(y, u, v, mats[name],
                               qp if qps is None else qps[name],
                               resize=resize)[0]
            for name, h, w, qp in rungs}


def ladder_encode_program(rungs: tuple[RungSpec, ...], src_h: int, src_w: int,
                          mesh: Mesh | None = None,
                          pallas: bool | None = None) -> tuple[Callable, dict]:
    """Resolve ``pallas`` (None -> VLOG_PALLAS) OUTSIDE the
    cache — the hevc_ladder deblock idiom: resolving inside would let
    two different config states share one compiled entry."""
    if pallas is None:
        pallas = use_pallas()
    return _ladder_encode_cached(rungs, src_h, src_w, mesh, bool(pallas))


@functools.lru_cache(maxsize=8)
def _ladder_encode_cached(rungs: tuple[RungSpec, ...], src_h: int, src_w: int,
                          mesh: Mesh | None,
                          pallas: bool) -> tuple[Callable, dict]:
    """The production one-pass ladder step the backend dispatches per batch.

    Returns (fn, mats) with ``fn(y, u, v, mats, qps)`` where ``qps`` maps
    rung name -> (n,) int32 per-frame QP. Output per rung: the four
    quantized-levels arrays (what host CAVLC needs) plus ``sse_y`` (n,)
    float32 over the display region — recon planes never leave the
    device, saving the dominant HBM->host transfer. Levels cross to the
    host as int16 (H.264 levels are 16-bit by spec constraint), halving
    the device->host bytes of the steady-state loop.

    Cached per (rungs, geometry, mesh): the jitted program and its staged
    matrices survive across backend runs, so a second video with the same
    shapes skips both retrace and XLA recompilation.

    With a mesh, the batch axis is shard_mapped over "data" (frames are
    independent in all-intra; zero steady-state collectives) — the
    multi-chip path of SURVEY.md §2d.5. Without one, a plain jit.
    """
    resize = ladder_resize(pallas)

    def local(y, u, v, mats, qps):
        out = {}
        for name, h, w, qp in rungs:
            levels, ry = _encode_rung(y, u, v, mats[name], qps[name],
                                      resize=resize)
            err = (levels["recon_y"][:, :h, :w].astype(jnp.float32)
                   - ry.astype(jnp.float32))
            out[name] = {
                "luma_dc": levels["luma_dc"].astype(jnp.int16),
                "luma_ac": levels["luma_ac"].astype(jnp.int16),
                "chroma_dc": levels["chroma_dc"].astype(jnp.int16),
                "chroma_ac": levels["chroma_ac"].astype(jnp.int16),
                "sse_y": jnp.sum(err * err, axis=(1, 2)),
            }
        return out

    if mesh is None:
        fn = jax.jit(local)
        # Stage the (up to ~100MB at 4K) matrix pytree to HBM once — jit
        # would otherwise re-upload host numpy args every batch.
        return fn, jax.device_put(ladder_matrices(rungs, src_h, src_w))
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P(), P("data")),
        out_specs=P("data"),
        check_vma=False,
    )
    mats = ladder_matrices(rungs, src_h, src_w)
    mats = jax.device_put(mats, NamedSharding(mesh, P()))
    return _jit_frames(fn, mesh), mats


def ladder_chain_program(rungs: tuple[RungSpec, ...], src_h: int, src_w: int,
                         search: int = 8, mesh: Mesh | None = None,
                         deblock: bool = False,
                         pallas: bool | None = None) -> tuple[Callable, dict]:
    """Resolve ``pallas`` outside the cache (see ladder_encode_program)."""
    if pallas is None:
        pallas = use_pallas()
    return _ladder_chain_cached(rungs, src_h, src_w, search, mesh,
                                deblock, bool(pallas))


@functools.lru_cache(maxsize=8)
def _ladder_chain_cached(rungs: tuple[RungSpec, ...], src_h: int, src_w: int,
                         search: int, mesh: Mesh | None,
                         deblock: bool, pallas: bool
                         ) -> tuple[Callable, dict]:
    """The I+P chain ladder step (GOP_MODE="p" production path).

    ``fn(y, u, v, mats, qps)`` with y/u/v shaped (n_chains, clen, ...) and
    ``qps`` mapping rung -> (n_chains, clen) int32. Each chain is one
    mini-GOP: frame 0 intra, frames 1..clen-1 P against the previous
    frame's reconstruction — a ``lax.scan`` over time whose every step is
    a full-frame-parallel encode, vmapped over chains. Chains are
    self-contained (each starts with an IDR), so the mesh path shards the
    CHAIN axis over "data" with zero steady-state collectives: inter
    prediction serializes frames within a chain, never across devices
    (SURVEY §2d.5 adapted for temporal dependence).

    With ``deblock`` the spec 8.7 in-loop filter (codecs/h264/deblock.py
    wavefront) runs on every reconstruction before it becomes the next
    frame's reference — slice headers must then signal idc=0
    (H264Encoder(deblock=True)), and SSE measures the filtered picture
    (what a decoder displays).

    **Device-side in-chain rate adaptation.**  ``fn`` takes a 6th arg
    ``rc`` mapping rung -> {"budget": f32 bytes/frame, "alpha": f32
    bytes/proxy-unit} — optional (default None) on the single-device
    jit path, REQUIRED (pass None explicitly for legacy behavior) when
    built over a mesh: shard_map's in_specs is a fixed 6-tuple.  The host controller observes once per chain
    dispatch, so a scene cut or noise burst used to ship a whole hot
    chain before any correction (measured 3-4x over budget for 24
    frames).  With ``rc``, the frame scan carries a byte balance: each
    frame's quantized levels yield a bits proxy (nnz + sum log2(1+|l|),
    the shape of CAVLC/CABAC coeff cost), ``alpha`` converts it to
    bytes, and the NEXT frame's QP gets ``trunc(balance/(3*budget))``
    clamped to [-1, +8] — pay debt aggressively (a burst raises QP one
    frame later, not one chain later), spend credit one QP at a time
    (the same asymmetry as backends/rate_control.py).  ``alpha`` is
    EMA-calibrated by the host from realized chain bytes; alpha==0
    (first dispatch) disables adjustment.  With ``rc`` the outputs gain
    "qp_eff" (n, clen) int16 — the QPs the entropy stage must signal —
    and "cost" (n, clen) f32 for the host's alpha update.

    Per rung output (int16 levels, device-only recon):
      i_luma_dc/(n,4,4) i_luma_ac i_chroma_dc i_chroma_ac   — frame 0
      p_luma (n, clen-1, mbh, mbw, 4,4,4,4), p_chroma_dc, p_chroma_ac
      mv (n, clen-1, mbh, mbw, 2) int16, sse_y (n, clen) float32
    """
    from vlog_tpu.codecs.h264.deblock import deblock_frame, intra_bs, p_bs
    from vlog_tpu.codecs.h264.encoder import encode_frame
    from vlog_tpu.codecs.h264.inter import encode_p_frame

    from vlog_tpu.ops.bitproxy import cost_proxy

    # per-chain reduction: each array is (n, ...) -> (n,)
    _proxy = functools.partial(cost_proxy, batch_ndim=1)

    resize = ladder_resize(pallas)

    def one_rung(y, u, v, rung_mats, qps, h, w, rcr=None):
        # y: (n, clen, H, W) local chains; resize whole block at once
        n, clen = y.shape[0], y.shape[1]
        flat = lambda p: p.reshape((n * clen,) + p.shape[2:])
        ry, ru, rv = resize(flat(y), flat(u), flat(v), rung_mats)
        py, pu, pv = _pad_mb(ry, ru, rv)
        unflat = lambda p: p.reshape((n, clen) + p.shape[1:])
        py, pu, pv = unflat(py), unflat(pu), unflat(pv)
        ry = unflat(ry)
        mbh, mbw = py.shape[-2] // 16, py.shape[-1] // 16

        with jax.named_scope("ladder.intra"):
            i_out = jax.vmap(
                lambda a, b, c, q: encode_frame(a, b, c, qp=q)
            )(py[:, 0], pu[:, 0], pv[:, 0], qps[:, 0])
        i_rec = (i_out["recon_y"], i_out["recon_u"], i_out["recon_v"])
        if deblock:
            ibs_v, ibs_h = intra_bs(mbh, mbw)
            i_rec = jax.vmap(
                lambda a, b, c, q: deblock_frame(
                    a, b, c, qp=q, bs_v=ibs_v, bs_h=ibs_h)
            )(*i_rec, qps[:, 0])
            i_rec = tuple(p.astype(jnp.uint8) for p in i_rec)
        sse0 = jnp.sum(
            (i_rec[0][:, :h, :w].astype(jnp.float32)
             - ry[:, 0].astype(jnp.float32)) ** 2, axis=(1, 2))
        if rcr is not None:
            budget = jnp.maximum(
                jnp.asarray(rcr["budget"], jnp.float32), 1.0)
            alpha = jnp.asarray(rcr["alpha"], jnp.float32)
            cost0 = _proxy(i_out["luma_dc"], i_out["luma_ac"],
                           i_out["chroma_dc"], i_out["chroma_ac"])
            # balance starts at ZERO: the I frame's overspend vs the
            # per-frame budget is PLANNED (the -2 anchor pays off down
            # the chain) and the host's outer loop already accounts for
            # it across chains — charging it here would tax the first P
            # frames of every chain with +1..2 QP right after each IDR
            bal0 = jnp.zeros_like(cost0)

        def step(carry, xs):
            if rcr is None:
                ref_y, ref_u, ref_v = carry
                cy, cu, cv, q, src_y = xs
            else:
                (ref_y, ref_u, ref_v), bal = carry
                cy, cu, cv, q_plan, src_y = xs
                adj = jnp.clip(jnp.trunc(bal / (3.0 * budget)),
                               -1.0, 8.0).astype(jnp.int32)
                q = jnp.clip(q_plan + adj, 10, 51)
            pout = jax.vmap(
                lambda a, b, c, r1, r2, r3, qq: encode_p_frame(
                    a, b, c, r1, r2, r3, qp=qq, search=search)
            )(cy, cu, cv, ref_y, ref_u, ref_v, q)
            rec = (pout["recon_y"], pout["recon_u"], pout["recon_v"])
            if deblock:
                # bS from what the decoder will see: the (decimated)
                # coded levels and the per-MB motion field
                nz = jnp.any(pout["luma"] != 0, axis=(-1, -2))
                nz4 = jnp.transpose(nz, (0, 1, 3, 2, 4)).reshape(
                    nz.shape[0], 4 * mbh, 4 * mbw)
                bsv, bsh = jax.vmap(p_bs)(nz4, pout["mv"])
                rec = jax.vmap(
                    lambda a, b, c, q2, bv, bh: deblock_frame(
                        a, b, c, qp=q2, bs_v=bv, bs_h=bh)
                )(*rec, q, bsv, bsh)
                rec = tuple(p.astype(jnp.uint8) for p in rec)
            sse = jnp.sum(
                (rec[0][:, :h, :w].astype(jnp.float32)
                 - src_y.astype(jnp.float32)) ** 2, axis=(1, 2))
            out = {
                "luma": pout["luma"].astype(jnp.int16),
                "chroma_dc": pout["chroma_dc"].astype(jnp.int16),
                "chroma_ac": pout["chroma_ac"].astype(jnp.int16),
                "mv": pout["mv"].astype(jnp.int16),
                "sse": sse,
            }
            if rcr is None:
                return (rec, out)
            cost = _proxy(pout["luma"], pout["chroma_dc"],
                          pout["chroma_ac"])
            # anti-windup: credit bottoms at 3 frames of budget (a long
            # easy stretch must not delay the response to a burst by
            # more than a frame), debt tops at what +8 QP can repay
            bal = jnp.clip(
                bal + jnp.where(alpha > 0, cost * alpha - budget, 0.0),
                -3.0 * budget, 30.0 * budget)
            out["qp_eff"] = q.astype(jnp.int16)
            out["cost"] = cost
            return ((rec, bal), out)

        t_axis = lambda p: jnp.moveaxis(p[:, 1:], 1, 0)  # (clen-1, n, ...)
        _, scanned = jax.lax.scan(
            step,
            i_rec if rcr is None else (i_rec, bal0),
            (t_axis(py), t_axis(pu), t_axis(pv),
             jnp.moveaxis(qps[:, 1:], 1, 0), t_axis(ry)),
        )
        chain_first = lambda p: jnp.moveaxis(p, 0, 1)    # (n, clen-1, ...)
        out = {
            "i_luma_dc": i_out["luma_dc"].astype(jnp.int16),
            "i_luma_ac": i_out["luma_ac"].astype(jnp.int16),
            "i_chroma_dc": i_out["chroma_dc"].astype(jnp.int16),
            "i_chroma_ac": i_out["chroma_ac"].astype(jnp.int16),
            "p_luma": chain_first(scanned["luma"]),
            "p_chroma_dc": chain_first(scanned["chroma_dc"]),
            "p_chroma_ac": chain_first(scanned["chroma_ac"]),
            "mv": chain_first(scanned["mv"]),
            "sse_y": jnp.concatenate(
                [sse0[:, None], chain_first(scanned["sse"])], axis=1),
        }
        if rcr is not None:
            out["qp_eff"] = jnp.concatenate(
                [qps[:, :1].astype(jnp.int16),
                 chain_first(scanned["qp_eff"])], axis=1)
            out["cost"] = jnp.concatenate(
                [cost0[:, None], chain_first(scanned["cost"])], axis=1)
        return out

    def local(y, u, v, mats, qps, rc=None):
        return {name: one_rung(y, u, v, mats[name], qps[name], h, w,
                               None if rc is None else rc[name])
                for name, h, w, qp in rungs}

    mats = ladder_matrices(rungs, src_h, src_w)
    if mesh is None:
        return jax.jit(local), jax.device_put(mats)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P(), P("data"), P()),
        out_specs=P("data"),
        check_vma=False,
    )
    return _jit_frames(fn, mesh), jax.device_put(mats, NamedSharding(mesh, P()))


class GridProgram:
    """One-call dispatch of a ladder over a (data × rung) grid.

    Owns one compiled program per rung column (each built over the
    column's 1-D data submesh with only that column's resize matrices
    staged) and performs the input staging itself: the source frames
    replicate into every column (the rung-axis replication), per-rung
    QP/RC state routes to the owning column, and the merged output dict
    leaves each rung's arrays resident on its owning column — so the
    executor's per-rung async d2h pulls come off different devices.

    Degenerate shapes collapse to the classic paths: ``grid=None`` is
    the single-chip jit program (host numpy in, default device), and a
    ``Nx1`` grid is the 1-D data mesh — one column, all rungs, same
    program the pre-grid backends built. Byte identity across shapes
    follows from rung independence: a column computes exactly the
    restriction of the full program to its rung subset.
    """

    def __init__(self, columns: tuple, data: int, label: str, chain: bool):
        # columns: ((names, mesh_or_None, fn, mats), ...)
        self.columns = columns
        self.data = data          # data-axis width (pad_batch target)
        self.label = label        # e.g. "2x4"; "1x1" single-chip
        self._chain = chain

    def dispatch(self, y, u, v, qps: dict, rc: dict | None = None):
        """Stage + run every column; returns {rung_name: outputs}."""
        outs = {}
        for names, mesh, fn, mats in self.columns:
            if mesh is None:
                cy, cu, cv = y, u, v
                cq = {n: qps[n] for n in names}
            else:
                cy, cu, cv = shard_frames(mesh, y, u, v)
                cq = {n: shard_frames(mesh, qps[n])[0] for n in names}
            if self._chain:
                crc = None if rc is None else {n: rc[n] for n in names}
                outs.update(fn(cy, cu, cv, mats, cq, crc))
            else:
                outs.update(fn(cy, cu, cv, mats, cq))
        return outs


def ladder_encode_grid(rungs: tuple[RungSpec, ...], src_h: int, src_w: int,
                       grid: RungGrid | None = None,
                       pallas: bool | None = None) -> GridProgram:
    """Grid-wide intra ladder: per-column encode programs.

    ``pallas`` resolves (None -> VLOG_PALLAS) here, outside the
    caches, so the resolved plane keys both this cache and the
    per-column program cache.
    """
    if pallas is None:
        pallas = use_pallas()
    return _ladder_encode_grid_cached(rungs, src_h, src_w, grid,
                                      bool(pallas))


@functools.lru_cache(maxsize=8)
def _ladder_encode_grid_cached(rungs: tuple[RungSpec, ...], src_h: int,
                               src_w: int, grid: RungGrid | None,
                               pallas: bool) -> GridProgram:
    """Cached per (rungs, geometry, grid, pallas) on top of the
    per-column program cache, so regenerating the same grid reuses
    every compiled column."""
    if grid is None:
        fn, mats = _ladder_encode_cached(rungs, src_h, src_w, None, pallas)
        names = tuple(r[0] for r in rungs)
        return GridProgram(((names, None, fn, mats),), 1, "1x1", False)
    cols = []
    for col in grid.columns:
        fn, mats = _ladder_encode_cached(col.rungs, src_h, src_w,
                                         col.mesh, pallas)
        cols.append((col.names, col.mesh, fn, mats))
    return GridProgram(tuple(cols), grid.data, grid.label, False)


def ladder_chain_grid(rungs: tuple[RungSpec, ...], src_h: int, src_w: int,
                      search: int = 8, grid: RungGrid | None = None,
                      deblock: bool = False,
                      pallas: bool | None = None) -> GridProgram:
    """Grid-wide I+P chain ladder: per-column chain programs. ``pallas``
    resolves outside the caches (see ladder_encode_grid)."""
    if pallas is None:
        pallas = use_pallas()
    return _ladder_chain_grid_cached(rungs, src_h, src_w, search, grid,
                                     deblock, bool(pallas))


@functools.lru_cache(maxsize=8)
def _ladder_chain_grid_cached(rungs: tuple[RungSpec, ...], src_h: int,
                              src_w: int, search: int,
                              grid: RungGrid | None, deblock: bool,
                              pallas: bool) -> GridProgram:
    if grid is None:
        fn, mats = _ladder_chain_cached(rungs, src_h, src_w, search,
                                        None, deblock, pallas)
        names = tuple(r[0] for r in rungs)
        return GridProgram(((names, None, fn, mats),), 1, "1x1", True)
    cols = []
    for col in grid.columns:
        fn, mats = _ladder_chain_cached(col.rungs, src_h, src_w, search,
                                        col.mesh, deblock, pallas)
        cols.append((col.names, col.mesh, fn, mats))
    return GridProgram(tuple(cols), grid.data, grid.label, True)


def single_chip_ladder(rungs: tuple[RungSpec, ...], src_h: int, src_w: int,
                       pallas: bool | None = None) -> tuple[Callable, dict]:
    """Jitted one-device ladder step + its matrices pytree.

    Returns (fn, mats): call ``fn(y, u, v, mats)``.
    """
    if pallas is None:
        pallas = use_pallas()
    fn = jax.jit(functools.partial(ladder_local, rungs=rungs,
                                   resize=ladder_resize(bool(pallas))))
    return fn, ladder_matrices(rungs, src_h, src_w)


def sharded_ladder_levels(mesh: Mesh, rungs: tuple[RungSpec, ...],
                          src_h: int, src_w: int,
                          pallas: bool | None = None) -> tuple[Callable, dict]:
    """Sharded ladder step for one mesh + rung set + source geometry.

    Returns (fn, mats). ``fn(y, u, v, mats)``: leading frame axis must
    divide by the data-axis size; outputs are sharded on "data"; ``mats``
    is replicated.
    """
    if pallas is None:
        pallas = use_pallas()
    fn = shard_map(
        functools.partial(ladder_local, rungs=rungs,
                          resize=ladder_resize(bool(pallas))),
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P()),
        out_specs=P("data"),
        # encode_frame's row scans start from constant (replicated) carries
        # that become device-varying after the first step; skip the VMA
        # type check rather than pcast every carry init.
        check_vma=False,
    )
    mats = ladder_matrices(rungs, src_h, src_w)
    mats = jax.device_put(mats, NamedSharding(mesh, P()))
    return _jit_frames(fn, mesh), mats


def sharded_ladder_step(mesh: Mesh, rungs: tuple[RungSpec, ...],
                        src_h: int, src_w: int,
                        pallas: bool | None = None) -> tuple[Callable, dict]:
    """Ladder step + per-rung quality stats (the "training step" analog).

    Besides the levels, computes mean PSNR-Y per rung against the resized
    source — an all-device ``psum`` over ICI, exercising the collective
    path the way a training step's gradient reduction would.

    The returned fn takes ``(y, u, v, mats, valid)`` where ``valid`` is a
    (n,) float32 0/1 mask sharded like the frames: pad_batch's duplicated
    flush frames get 0 so they never bias the quality stats.
    """
    def local(y, u, v, mats, valid):
        out = {}
        stats = {}
        for name, h, w, qp in rungs:
            levels, ry = _encode_rung(y, u, v, mats[name], qp)
            # PSNR over the display region only (padding is replicated edge)
            err = (levels["recon_y"][:, :h, :w].astype(jnp.float32)
                   - ry.astype(jnp.float32))
            local_mse = jnp.sum(valid * jnp.mean(err * err, axis=(1, 2)))
            total_mse = jax.lax.psum(local_mse, "data")
            total_n = jax.lax.psum(jnp.sum(valid), "data")
            mse = total_mse / jnp.maximum(total_n, 1.0)
            stats[name] = 10.0 * jnp.log10(255.0 ** 2 / jnp.maximum(mse, 1e-6))
            out[name] = levels
        return out, stats

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P(), P("data")),
        out_specs=(P("data"), P()),
        check_vma=False,
    )
    mats = ladder_matrices(rungs, src_h, src_w)
    mats = jax.device_put(mats, NamedSharding(mesh, P()))
    return jax.jit(fn), mats


def valid_mask(n_total: int, n_real: int):
    """0/1 mask marking pad_batch's duplicated trailing frames invalid."""
    return (jnp.arange(n_total) < n_real).astype(jnp.float32)
