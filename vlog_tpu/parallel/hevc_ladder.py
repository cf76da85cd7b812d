"""Fused HEVC chain ladder: every hvc1 rung from one dispatch.

Round-3's HEVC path dispatched the chain DSP once per rung per chain
(backends/hevc_path.py admitted the gap) — the exact one-encode-per-rung
shape the H.264 ladder was built to kill (SURVEY §2d.2). This module
mirrors ``parallel/ladder.py``'s chain program for HEVC: one XLA program
resizes the source once per rung, runs the I+P chain DSP for ALL rungs,
and ships int16 levels + per-frame SSE — reconstructions never leave the
device (they fed PSNR on host before, a large d2h tax at 4K).

Sharding matches the H.264 ladder: chains are self-contained mini-GOPs
(IDR-anchored), so the mesh shards the CHAIN axis over "data" with zero
steady-state collectives (SURVEY §2d.5).

Production runs ``partitions=False`` (config.HEVC_PARTITIONS): every CTB
is a 2Nx2N inter CU, which is also the C entropy coder's contract, so
the program ships no partition map and the host packs at C speed.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vlog_tpu.codecs.hevc.jax_core import encode_chain_dsp
from vlog_tpu.codecs.hevc.syntax import CTB
from vlog_tpu.ops.pallas_ladder import ladder_resize, use_pallas
from vlog_tpu.parallel.ladder import (GridProgram, RungSpec, _jit_frames,
                                      ladder_matrices)
from vlog_tpu.parallel.mesh import RungGrid, shard_map


def _pad_ctb(y, u, v):
    """Edge-pad a (n, H, W) YUV420 batch to CTB (32) alignment."""
    h, w = y.shape[-2], y.shape[-1]
    ph, pw = (-h) % CTB, (-w) % CTB
    if ph or pw:
        y = jnp.pad(y, ((0, 0), (0, ph), (0, pw)), mode="edge")
        u = jnp.pad(u, ((0, 0), (0, ph // 2), (0, pw // 2)), mode="edge")
        v = jnp.pad(v, ((0, 0), (0, ph // 2), (0, pw // 2)), mode="edge")
    return y, u, v


def hevc_chain_ladder_program(rungs: tuple[RungSpec, ...], src_h: int,
                              src_w: int, search: int = 16,
                              mesh: Mesh | None = None,
                              deblock: bool | None = None,
                              pallas: bool | None = None
                              ) -> tuple[Callable, dict]:
    """Resolve ``deblock`` (None -> config.HEVC_DEBLOCK) and ``pallas``
    (None -> VLOG_PALLAS) OUTSIDE the cache: resolving inside
    would let two different config states share one cache entry (tests
    monkeypatch the flags)."""
    if deblock is None:
        from vlog_tpu import config

        deblock = config.HEVC_DEBLOCK
    if pallas is None:
        pallas = use_pallas()
    return _hevc_chain_ladder_cached(rungs, src_h, src_w, search, mesh,
                                     bool(deblock), bool(pallas))


@functools.lru_cache(maxsize=8)
def _hevc_chain_ladder_cached(rungs: tuple[RungSpec, ...], src_h: int,
                              src_w: int, search: int,
                              mesh: Mesh | None,
                              deblock: bool,
                              pallas: bool
                              ) -> tuple[Callable, dict]:
    """``fn(y, u, v, mats, qps)`` with y/u/v (n_chains, clen, ...) uint8
    and ``qps`` mapping rung -> (n_chains, clen) int32 (frame 0's value
    is the pre-offset chain QP: the program applies the I-frame -2
    anchor itself, mirroring HevcEncoder.encode_chain).

    Per rung output:
      i_luma (n, R, C, 32, 32) int16, i_cb/i_cr (n, R/?, ...) int16
      p_luma (n, clen-1, R, C, 32, 32) int16, p_cb, p_cr
      mv (n, clen-1, 2R, 2C, 2) int16 (quarter-pel, (y, x))
      sse_y (n, clen) float32 over the display region
    """

    resize = ladder_resize(pallas)

    def one_rung(y, u, v, rung_mats, qps, h, w, rcr=None):
        n, clen = y.shape[0], y.shape[1]
        flat = lambda p: p.reshape((n * clen,) + p.shape[2:])
        ry, ru, rv = resize(flat(y), flat(u), flat(v), rung_mats)
        py, pu, pv = _pad_ctb(ry, ru, rv)
        unflat = lambda p: p.reshape((n, clen) + p.shape[1:])
        py, pu, pv = unflat(py), unflat(pu), unflat(pv)

        def one_chain(cy, cu, cv, q):
            qp_i = jnp.maximum(10, q[0] - 2)
            qp_p = q[1:] if clen > 1 else q
            res = encode_chain_dsp(cy, cu, cv, search, qp_i, qp_p,
                                   False, deblock, rcr)
            (intra, recon0), (p32, _, _, mvs, precons) = res[0], res[1]
            rcout = res[2] if rcr is not None else None
            # display-region SSE per frame (recons stay on device)
            r0 = recon0[0][:h, :w].astype(jnp.float32)
            sse0 = jnp.sum((r0 - cy[0][:h, :w].astype(jnp.float32)) ** 2)
            if clen > 1:
                pry = precons[0][:, :h, :w].astype(jnp.float32)
                ssep = jnp.sum(
                    (pry - cy[1:, :h, :w].astype(jnp.float32)) ** 2,
                    axis=(1, 2))
                sse = jnp.concatenate([sse0[None], ssep])
            else:
                p32 = tuple(jnp.zeros((0,) + a.shape, a.dtype)
                            for a in intra)
                mvs = jnp.zeros((0, 1, 1, 2), jnp.int32)
                sse = sse0[None]
            out = {
                "i_luma": intra[0].astype(jnp.int16),
                "i_cb": intra[1].astype(jnp.int16),
                "i_cr": intra[2].astype(jnp.int16),
                "p_luma": p32[0].astype(jnp.int16),
                "p_cb": p32[1].astype(jnp.int16),
                "p_cr": p32[2].astype(jnp.int16),
                "mv": mvs.astype(jnp.int16),
                "sse_y": sse,
            }
            if rcr is not None:
                # entropy_chain re-derives the I anchor from slot 0, so
                # qp_eff[0] carries the PLAN value q[0]
                out["qp_eff"] = jnp.concatenate(
                    [q[:1], rcout["qp_eff"]]).astype(jnp.int16)
                out["cost"] = rcout["cost"]
            return out

        return jax.vmap(one_chain)(py, pu, pv, qps)

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
    return _jit_frames(fn, mesh), jax.device_put(mats,
                                                 NamedSharding(mesh, P()))


def hevc_chain_ladder_grid(rungs: tuple[RungSpec, ...], src_h: int,
                           src_w: int, search: int = 16,
                           grid: RungGrid | None = None,
                           deblock: bool | None = None,
                           pallas: bool | None = None) -> GridProgram:
    """Grid-wide HEVC chain ladder: per-column programs over a
    (data × rung) grid, same dispatch surface as the H.264 grids.

    ``deblock``/``pallas`` resolve (None -> config) here, outside the
    caches, for the same reason as :func:`hevc_chain_ladder_program`.
    """
    if deblock is None:
        from vlog_tpu import config

        deblock = config.HEVC_DEBLOCK
    if pallas is None:
        pallas = use_pallas()
    return _hevc_grid_cached(rungs, src_h, src_w, search, grid,
                             bool(deblock), bool(pallas))


@functools.lru_cache(maxsize=8)
def _hevc_grid_cached(rungs: tuple[RungSpec, ...], src_h: int, src_w: int,
                      search: int, grid: RungGrid | None,
                      deblock: bool, pallas: bool) -> GridProgram:
    if grid is None:
        fn, mats = _hevc_chain_ladder_cached(rungs, src_h, src_w, search,
                                             None, deblock, pallas)
        names = tuple(r[0] for r in rungs)
        return GridProgram(((names, None, fn, mats),), 1, "1x1", True)
    cols = []
    for col in grid.columns:
        fn, mats = _hevc_chain_ladder_cached(col.rungs, src_h, src_w,
                                             search, col.mesh, deblock,
                                             pallas)
        cols.append((col.names, col.mesh, fn, mats))
    return GridProgram(tuple(cols), grid.data, grid.label, True)
