"""Raw-speed plane gates: Pallas byte-identity, paged KV pool, compile
cache, quantized decode, and the pallasshim containment rule.

The Pallas matrix is the load-bearing contract: the fused kernel (in
interpret mode on this CPU VM — the same kernel body Mosaic lowers on
real TPU) must produce BYTE-IDENTICAL output trees to the XLA resize
path across grid shapes x ladder depths x {h264 intra, h264 chain,
hevc chain}. Identity is asserted on the full output pytrees (levels,
motion vectors, SSE — not just the resized planes), so any divergence
anywhere downstream of the resize fails loudly.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vlog_tpu.parallel.mesh import MeshShape, rung_grid

# 64x96 source with no identity rung, so EVERY rung exercises the
# kernel (identity rungs carry mats=None and bypass the fused plane);
# depth-d ladders are prefixes.
_SRC_H, _SRC_W = 64, 96
_RUNGS3 = (("48p", 48, 64, 28), ("32p", 32, 48, 29), ("24p", 24, 32, 30))


def _grid(shape: tuple[int, int] | None, rungs):
    if shape is None:
        return None
    return rung_grid(rungs, MeshShape(*shape), list(jax.devices()))


def _frames(n: int):
    rng = np.random.default_rng(42)
    y = rng.integers(0, 256, (n, _SRC_H, _SRC_W)).astype(np.uint8)
    u = rng.integers(0, 256, (n, _SRC_H // 2, _SRC_W // 2)).astype(np.uint8)
    v = rng.integers(0, 256, (n, _SRC_H // 2, _SRC_W // 2)).astype(np.uint8)
    return y, u, v


def _chains(n: int, clen: int):
    y, u, v = _frames(n * clen)
    shp = lambda p: p.reshape((n, clen) + p.shape[1:])
    return shp(y), shp(u), shp(v)


def _assert_tree_identical(a, b):
    """Byte-for-byte equality over two output pytrees."""
    flat_a, tree_a = jax.tree_util.tree_flatten_with_path(a)
    flat_b, tree_b = jax.tree_util.tree_flatten_with_path(b)
    assert tree_a == tree_b
    for (path, xa), (_, xb) in zip(flat_a, flat_b):
        where = jax.tree_util.keystr(path)
        xa, xb = np.asarray(xa), np.asarray(xb)
        assert xa.dtype == xb.dtype and xa.shape == xb.shape, where
        np.testing.assert_array_equal(xa, xb, err_msg=where)


# The matrix: depth sweep on single-chip, 2-D shapes (data x rung) on
# the 8-device CPU mesh at mixed depths. Shapes include multi-rung
# columns (depth 3 on rung-width 2) and data-only width 4.
_MATRIX = (
    [(d, None) for d in (1, 2, 3)]
    + [(1, (2, 1)), (2, (2, 2)), (3, (2, 1)), (3, (2, 2)), (3, (4, 1))]
)

# The intra dispatcher runs the FULL matrix in tier-1 — it is the
# cheapest spelling and the fused kernel sees identical geometry from
# all three dispatchers. Chain/HEVC programs are compile-heavy
# (~20-35s each on this VM), so tier-1 keeps their corner cases and
# the full sweeps ride the `slow` marker (run with `-m slow`).
_CHAIN_FAST = {(1, None), (2, (2, 2))}
_CHAIN_MATRIX = [
    pytest.param(d, s,
                 marks=[] if (d, s) in _CHAIN_FAST else [pytest.mark.slow])
    for d, s in _MATRIX
]


@pytest.mark.parametrize("depth,shape", _MATRIX)
def test_pallas_intra_byte_identity(depth, shape):   # slowlane-ok: intra programs are the cheap spelling — full matrix is budgeted for tier-1 (see _CHAIN_FAST note)
    from vlog_tpu.parallel.ladder import ladder_encode_grid

    rungs = _RUNGS3[:depth]
    y, u, v = _frames(4)
    qps = {name: np.full(4, qp, np.int32) for name, _, _, qp in rungs}
    outs = {}
    for pallas in (False, True):
        prog = ladder_encode_grid(rungs, _SRC_H, _SRC_W,
                                  _grid(shape, rungs), pallas=pallas)
        outs[pallas] = jax.block_until_ready(prog.dispatch(y, u, v, qps))
    _assert_tree_identical(outs[False], outs[True])


@pytest.mark.parametrize("depth,shape", _CHAIN_MATRIX)
def test_pallas_chain_byte_identity(depth, shape):
    from vlog_tpu.parallel.ladder import ladder_chain_grid

    rungs = _RUNGS3[:depth]
    n, clen = 4, 2
    y, u, v = _chains(n, clen)
    qps = {name: np.full((n, clen), qp, np.int32)
           for name, _, _, qp in rungs}
    rc = {name: {"budget": np.float32(2000.0), "alpha": np.float32(0.5)}
          for name, _, _, _ in rungs}
    outs = {}
    for pallas in (False, True):
        prog = ladder_chain_grid(rungs, _SRC_H, _SRC_W, search=2,
                                 grid=_grid(shape, rungs), deblock=False,
                                 pallas=pallas)
        outs[pallas] = jax.block_until_ready(
            prog.dispatch(y, u, v, qps, rc))
    _assert_tree_identical(outs[False], outs[True])


# HEVC compiles the heaviest per-rung programs; sweep the matrix ends.
@pytest.mark.parametrize("depth,shape", [
    (1, None),
    pytest.param(3, None, marks=pytest.mark.slow),
    pytest.param(2, (2, 2), marks=pytest.mark.slow),
    pytest.param(3, (2, 1), marks=pytest.mark.slow),
])
def test_pallas_hevc_byte_identity(depth, shape):
    from vlog_tpu.parallel.hevc_ladder import hevc_chain_ladder_grid

    rungs = _RUNGS3[:depth]
    n, clen = 4, 2
    y, u, v = _chains(n, clen)
    qps = {name: np.full((n, clen), qp, np.int32)
           for name, _, _, qp in rungs}
    outs = {}
    for pallas in (False, True):
        prog = hevc_chain_ladder_grid(rungs, _SRC_H, _SRC_W, search=2,
                                      grid=_grid(shape, rungs),
                                      deblock=False, pallas=pallas)
        outs[pallas] = jax.block_until_ready(prog.dispatch(y, u, v, qps))
    _assert_tree_identical(outs[False], outs[True])


def test_fused_resize_plane_matches_xla_directly():
    """Kernel-level identity on geometries the ladder never builds:
    odd-block heights (30, 66), upscale on one axis, 4-D leading dims."""
    from vlog_tpu.ops.pallas_ladder import fused_resize_plane
    from vlog_tpu.ops.resize import apply_resize_matrices, resample_matrix

    rng = np.random.default_rng(0)
    for (sh, sw, dh, dw) in ((96, 128, 48, 64), (64, 96, 36, 48),
                             (66, 128, 30, 110)):
        x = rng.integers(0, 256, (2, 3, sh, sw)).astype(np.uint8)
        a_h = jnp.asarray(resample_matrix(sh, dh))
        a_w = jnp.asarray(resample_matrix(sw, dw))
        got = np.asarray(fused_resize_plane(x, a_h, a_w))
        ref = np.asarray(apply_resize_matrices(x, a_h, a_w))
        np.testing.assert_array_equal(got, ref,
                                      err_msg=str((sh, sw, dh, dw)))
        assert got.shape == (2, 3, dh, dw) and got.dtype == np.uint8


def test_use_pallas_policy(monkeypatch):
    from vlog_tpu import config
    from vlog_tpu.ops import pallas_ladder as pal

    assert pal.use_pallas("0") is False
    assert pal.use_pallas("off") is False
    # "1" means the kernel, with no probe standing between the knob and
    # the plane: a refusal must surface where the kernel is traced
    assert pal.use_pallas("1") is True
    assert not hasattr(pal, "pallas_available")
    # auto never fuses: Mosaic refuses the kernel as written on a TPU
    # (PR 21) and interpret mode is a correctness vehicle, not a path
    assert pal.use_pallas("auto") is False
    monkeypatch.setattr(config, "PALLAS", "auto")
    assert pal.use_pallas() is False
    monkeypatch.setattr(config, "PALLAS", "1")
    assert pal.use_pallas() is True


def test_fused_plane_never_falls_back(monkeypatch):
    """A lowering failure raises out of ``fused_resize_plane``; there is
    no XLA path behind it (what ``VLOG_PALLAS=1`` on a TPU relies on)."""
    from vlog_tpu.ops import pallas_ladder as pal
    from vlog_tpu.ops.resize import resample_matrix

    def refuse(*_a, **_k):
        raise NotImplementedError("Unsupported cast: uint8 -> float32")

    monkeypatch.setattr(pal.pl, "pallas_call", refuse)
    x = np.zeros((1, 32, 48), np.uint8)
    with pytest.raises(NotImplementedError, match="Unsupported cast"):
        pal.fused_resize_plane(x, jnp.asarray(resample_matrix(32, 16)),
                               jnp.asarray(resample_matrix(48, 24)))


def test_block_rows_exact_divisor():
    from vlog_tpu.ops.pallas_ladder import _block_rows

    for dst_h in (24, 30, 48, 66, 127, 128, 270, 1080, 2160):
        bh = _block_rows(dst_h)
        assert dst_h % bh == 0 and 1 <= bh <= 128
    assert _block_rows(128) == 128
    assert _block_rows(2160) == 120
    assert _block_rows(131) == 1          # prime > 128: row-at-a-time


# --------------------------------------------------------------------------
# plan_ladder_matrices memoization
# --------------------------------------------------------------------------

def test_plan_ladder_matrices_memoized():
    from vlog_tpu.ops import resize as rz

    rungs_hw = ((48, 64), (24, 32))
    a = rz.plan_ladder_matrices(96, 128, rungs_hw)
    b = rz.plan_ladder_matrices(96, 128, rungs_hw)
    # fresh dict per call (callers may mutate) over the SAME cached
    # matrices (no lanczos window recompute)
    assert a is not b
    assert a[(48, 64)][0][0] is b[(48, 64)][0][0]
    a[(48, 64)] = None                    # mutation must not poison
    c = rz.plan_ladder_matrices(96, 128, rungs_hw)
    assert c[(48, 64)] is not None
    # identity rungs and validation behave as before memoization
    assert rz.plan_ladder_matrices(96, 128, ((96, 128),))[(96, 128)] is None
    with pytest.raises(ValueError):
        rz.plan_ladder_matrices(95, 128, rungs_hw)
    with pytest.raises(ValueError):
        rz.plan_ladder_matrices(96, 128, ((47, 64),))


# --------------------------------------------------------------------------
# Quantized Whisper decode
# --------------------------------------------------------------------------

def _tiny_cfg():
    from vlog_tpu.asr.model import WhisperConfig

    return WhisperConfig(
        d_model=64, encoder_layers=2, decoder_layers=2,
        encoder_attention_heads=4, decoder_attention_heads=4,
        encoder_ffn_dim=128, decoder_ffn_dim=128, vocab_size=128,
        num_mel_bins=80, max_source_positions=1500,
        max_target_positions=448)


def test_quantize_params_int8_roundtrip():
    from vlog_tpu.asr.load import ModelLoadError, quantize_params
    from vlog_tpu.asr.model import QuantTensor, init_random_params

    params = init_random_params(_tiny_cfg(), seed=1)
    q = quantize_params(params, "int8")
    key = "model.decoder.layers.0.self_attn.q_proj.weight"
    qt = q[key]
    assert isinstance(qt, QuantTensor)
    assert qt.q.dtype == np.int8 and qt.q.shape == params[key].shape
    assert qt.scale.shape == (params[key].shape[0],)
    # dequant error bounded by half an int8 step per weight
    w = np.asarray(params[key])
    scale = np.asarray(qt.scale)[:, None]
    deq = np.asarray(qt.q, np.float32) * scale
    assert np.all(np.abs(deq - w) <= scale / 2 + 1e-9)
    # everything _linear does not consume stays f32 and object-shared
    for k in ("model.decoder.embed_tokens.weight",
              "model.encoder.conv1.weight",
              "model.decoder.layers.0.self_attn.q_proj.bias",
              "model.decoder.layer_norm.weight"):
        assert q[k] is params[k]
    # f32 is a pure passthrough; bf16 stores bf16; junk modes refuse
    assert quantize_params(params, "f32") is params
    assert quantize_params(params, "bf16")[key].dtype == jnp.bfloat16
    with pytest.raises(ModelLoadError):
        quantize_params(params, "int4")


def test_linear_dequant_on_use():
    from vlog_tpu.asr.load import quantize_params
    from vlog_tpu.asr.model import _linear

    rng = np.random.default_rng(3)
    w = rng.standard_normal((8, 16)).astype(np.float32) * 0.1
    bias = rng.standard_normal(8).astype(np.float32)
    x = jnp.asarray(rng.standard_normal((2, 16)), jnp.float32)
    full = {"model.x.fc1.weight": jnp.asarray(w),
            "model.x.fc1.bias": jnp.asarray(bias)}
    strip = lambda p: {k.replace("model.x.", ""): v for k, v in p.items()}
    ref = np.asarray(_linear(strip(full), "fc1", x))
    got = np.asarray(_linear(strip(quantize_params(full, "int8")),
                             "fc1", x))
    # arbitrary weights: int8 is approximate, bounded by the step size
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.05)


def test_resolve_quant():
    from vlog_tpu import config
    from vlog_tpu.asr.load import ModelLoadError, resolve_quant

    assert resolve_quant("int8") == "int8"
    assert resolve_quant("F32") == "f32"
    assert resolve_quant("") == "f32"
    assert resolve_quant("none") == "f32"
    assert resolve_quant(None) == config.WHISPER_QUANT
    with pytest.raises(ModelLoadError):
        resolve_quant("fp8")


def test_quant_identity_proxy_gate():
    """quality_bench --quant end to end: int8-grid weights decode
    token-identically to f32 (the WER-parity gate's identity proxy)."""
    import quality_bench as qb

    rec = qb.run_asr_quant(beam=1)
    assert rec["metric"] == "asr_wer_quant"
    assert rec["value"] == 0.0
    assert rec["identical_tokens"] is True


# --------------------------------------------------------------------------
# Paged KV-cache pool
# --------------------------------------------------------------------------

def test_kv_pool_reuse_counters():
    from vlog_tpu.asr.decode import KVCachePool
    from vlog_tpu.asr.model import DecoderCache

    cfg = _tiny_cfg()
    pool = KVCachePool()
    c1 = pool.lease(cfg, 2, 8)
    assert c1.k.shape == (2, 2, 4, 8, 16)   # (layers, B, H, max_len, hd)
    assert pool.stats() == {"allocs": 1, "reuses": 0, "retained": 0}
    pool.release(c1)
    assert pool.stats()["retained"] == 1
    c2 = pool.lease(cfg, 2, 8)
    assert c2 is c1                          # page served from the pool
    assert pool.stats() == {"allocs": 1, "reuses": 1, "retained": 0}
    c3 = pool.lease(cfg, 4, 8)               # different shape: fresh page
    assert c3.k.shape[1] == 4
    assert pool.stats()["allocs"] == 2 and pool.stats()["reuses"] == 1
    pool.release(c2)
    pool.release(c3)
    # retention is bounded across all shapes
    for _ in range(pool._MAX_PAGES + 3):
        pool.release(DecoderCache(k=c1.k, v=c1.v))
    assert pool.stats()["retained"] == pool._MAX_PAGES
    pool.reset()
    assert pool.stats() == {"allocs": 0, "reuses": 0, "retained": 0}


def test_generation_reuses_kv_pages_across_calls():
    """Two same-shape decodes: the second leases the first's returned
    page (reuse counter increments) and its tokens are unaffected by
    the dirty page contents (decoder_step masks to written positions)."""
    from vlog_tpu.asr import decode as dec
    from vlog_tpu.asr.model import init_random_params

    cfg = _tiny_cfg()
    params = init_random_params(cfg, seed=0)
    rng = np.random.default_rng(5)
    mel = jnp.asarray(rng.standard_normal((2, 80, 3000)), jnp.float32)
    prompt = jnp.asarray([3, 4], jnp.int32)
    zeros = jnp.zeros(cfg.vocab_size, jnp.float32)
    kw = dict(cfg=cfg, sot=3, eot=1, ts_begin=cfg.vocab_size - 2,
              no_speech=-1, max_new=8, timestamps=False, beam=1)

    def run():
        cache = dec.kv_pool.lease(cfg, 2, prompt.shape[0] + 8)
        toks, _, cache = dec._generate_beam_jit(params, mel, prompt, zeros,
                                                zeros, cache, **kw)
        dec.kv_pool.release(cache)
        return np.asarray(toks)

    dec.kv_pool.reset()
    try:
        t1 = run()
        stats = dec.kv_pool.stats()
        assert stats["allocs"] >= 1 and stats["retained"] >= 1
        t2 = run()
        assert dec.kv_pool.stats()["reuses"] >= 1
        np.testing.assert_array_equal(t1, t2)  # dirty page changed nothing
    finally:
        dec.kv_pool.reset()


# --------------------------------------------------------------------------
# Compile cache
# --------------------------------------------------------------------------

def _restore_jax_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as jcc

    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jcc.reset_cache()   # drop the cache object bound to the tmp dir


def test_compile_cache_policy(tmp_path, monkeypatch):
    """Placement is decided outside the program: jax's own
    JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; unset on
    CPU there is no cache (host-ISA AOT entries do not port)."""
    from vlog_tpu.parallel import compile_cache as cc

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    try:
        cc.reset_for_tests()
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert cc.ensure_compile_cache() is None
        assert updates == []
        cc.reset_for_tests()
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "xla"))
        armed = cc.ensure_compile_cache()
        assert armed == str(tmp_path / "xla")
        assert cc.ensure_compile_cache() == armed    # second call: no-op
        # reported, not set: jax read the variable itself at start-up;
        # the only thing set in code is the persistence floor
        assert updates == [
            ("jax_persistent_cache_min_compile_time_secs", 0.0)]
        assert not (tmp_path / "xla").exists()       # jax creates it
    finally:
        cc.reset_for_tests()


def test_compile_meter_counts_backend_compiles():
    from vlog_tpu.parallel import compile_cache as cc

    before = cc.compile_seconds()

    @jax.jit
    def f(x):
        return x * 2 + 1

    # a never-before-jitted shape forces a backend compile
    f(np.arange(1137, dtype=np.float32)).block_until_ready()
    assert cc.compile_seconds() > before


def test_compile_cache_serves_warm_recompiles(tmp_path, monkeypatch):
    """In-process warm-vs-cold: after jax.clear_caches() the second
    compile of the same program is a persistent-cache HIT, which skips
    the backend compile — the meter (which counts only backend
    compiles) must see (almost) nothing. The test plays the operator:
    it places the cache the way JAX_COMPILATION_CACHE_DIR would have at
    start-up; ensure_compile_cache must leave that placement alone."""
    from jax.experimental.compilation_cache import compilation_cache as jcc

    from vlog_tpu.parallel import compile_cache as cc

    try:
        cc.reset_for_tests()
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "xla"))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "xla"))
        jcc.reset_cache()
        assert cc.ensure_compile_cache() == str(tmp_path / "xla")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "xla")

        def f(x):
            return jnp.sin(x) * 3.0 + jnp.cos(x) @ jnp.ones((512, 512))

        x = np.ones((384, 512), np.float32)
        t0 = cc.compile_seconds()
        jax.block_until_ready(jax.jit(f)(x))
        cold = cc.compile_seconds() - t0
        assert cold > 0
        assert any((tmp_path / "xla").iterdir()), "no cache entry written"
        jax.clear_caches()
        t1 = cc.compile_seconds()
        jax.block_until_ready(jax.jit(f)(x))
        warm = cc.compile_seconds() - t1
        assert warm < 0.8 * cold, (cold, warm)
    finally:
        cc.reset_for_tests()
        jax.clear_caches()
        _restore_jax_cache_config()


_WARM_COLD_CHILD = textwrap.dedent("""\
    import json, time

    import numpy as np

    t0 = time.perf_counter()
    from vlog_tpu.parallel import compile_cache as cc
    from vlog_tpu.parallel.ladder import ladder_encode_program

    cc.ensure_compile_cache()
    rungs = (("48p", 48, 64, 28), ("24p", 24, 32, 30))
    fn, mats = ladder_encode_program(rungs, 96, 128, None, pallas=False)
    y = np.zeros((2, 96, 128), np.uint8)
    u = np.zeros((2, 48, 64), np.uint8)
    v = np.zeros((2, 48, 64), np.uint8)
    qps = {n: np.full(2, q, np.int32) for n, _, _, q in rungs}
    import jax
    jax.block_until_ready(fn(y, u, v, mats, qps))
    print(json.dumps({"compile_s": cc.compile_seconds(),
                      "wall_s": time.perf_counter() - t0}))
""")


@pytest.mark.slow
def test_compile_cache_bench_record(tmp_path):
    """The acceptance gate, measured the way production restarts hit it:
    two fresh processes sharing one JAX_COMPILATION_CACHE_DIR (jax's
    own variable). Warm-start metered compile_s must be <= 0.2x cold;
    the pair is appended as a labeled BENCH_compile.json record."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    runs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", _WARM_COLD_CHILD],
                           capture_output=True, text=True, timeout=600,
                           env=env, cwd=str(Path(__file__).parent.parent))
        assert r.returncode == 0, r.stderr[-2000:]
        runs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert (tmp_path / "xla").is_dir() and any((tmp_path / "xla").iterdir())
    assert cold["compile_s"] > 0
    ratio = warm["compile_s"] / cold["compile_s"]
    record = {
        "metric": "compile_cache_warm_ratio",
        "value": round(ratio, 4),
        "unit": "warm_compile_s_over_cold",
        "vs_baseline": 0.2,
        "cold_compile_s": round(cold["compile_s"], 3),
        "warm_compile_s": round(warm["compile_s"], 3),
        "cold_wall_s": round(cold["wall_s"], 3),
        "warm_wall_s": round(warm["wall_s"], 3),
        "platform": "cpu",
        "program": "ladder_encode_program(2 rungs, 96x128)",
    }
    out = Path(__file__).parent.parent / "BENCH_compile.json"
    existing = []
    if out.exists():
        try:
            loaded = json.loads(out.read_text())
            existing = loaded if isinstance(loaded, list) else [loaded]
        except ValueError:
            existing = []
    existing.append(record)
    out.write_text(json.dumps(existing, indent=1) + "\n")
    assert ratio <= 0.2, record


@pytest.mark.slow
def test_asr_quant_microbench():
    """int8 vs bf16 decode throughput at the relaxed (WER-parity) gate,
    appended to BENCH_asr.json as a labeled record.

    int8's win is HBM weight streaming — a TPU property. On this CPU VM
    the int8 path pays an extra int->float convert per step with no
    bandwidth to save, so the >= 1.2x windows/sec gate is asserted only
    on real TPU; CPU runs record the measured ratio under
    ``gate: tpu_only`` so the trajectory still tracks it honestly.
    """
    import time

    from vlog_tpu.asr import decode as dec
    from vlog_tpu.asr.load import quantize_params
    from vlog_tpu.asr.model import WhisperConfig, init_random_params
    from vlog_tpu.parallel.dryrun import _append_records

    cfg = WhisperConfig(
        d_model=256, encoder_layers=4, decoder_layers=4,
        encoder_attention_heads=4, decoder_attention_heads=4,
        encoder_ffn_dim=1024, decoder_ffn_dim=1024, vocab_size=512,
        num_mel_bins=80, max_source_positions=1500,
        max_target_positions=448)
    params = init_random_params(cfg, seed=0)
    rng = np.random.default_rng(7)
    windows = 8
    mel = jnp.asarray(rng.standard_normal((windows, 80, 3000)),
                      jnp.float32)
    prompt = jnp.asarray([3, 4], jnp.int32)
    zeros = jnp.zeros(cfg.vocab_size, jnp.float32)
    max_new = 32
    kw = dict(cfg=cfg, sot=3, eot=1, ts_begin=cfg.vocab_size - 2,
              no_speech=-1, max_new=max_new, timestamps=False, beam=1)

    def wps(p, reps=3):
        def once():
            cache = dec.kv_pool.lease(cfg, windows, 2 + max_new)
            toks, _, cache = dec._generate_beam_jit(p, mel, prompt, zeros,
                                                    zeros, cache, **kw)
            jax.block_until_ready(toks)
            dec.kv_pool.release(cache)

        once()                            # compile outside the clock
        t0 = time.perf_counter()
        for _ in range(reps):
            once()
        return windows / ((time.perf_counter() - t0) / reps)

    dec.kv_pool.reset()
    try:
        bf16_wps = wps(quantize_params(params, "bf16"))
        int8_wps = wps(quantize_params(params, "int8"))
    finally:
        dec.kv_pool.reset()
    on_tpu = jax.default_backend() == "tpu"
    ratio = int8_wps / bf16_wps
    record = {
        "metric": "asr_int8_windows_per_second",
        "value": round(int8_wps, 2),
        "unit": "windows/s",
        "vs_baseline": round(ratio, 3),
        "bf16_windows_per_second": round(bf16_wps, 2),
        "quant": "int8",
        "wer_gate": "identity_proxy (quality_bench --quant, WER 0.0)",
        "gate": "int8>=1.2x bf16" if on_tpu else "tpu_only",
        "platform": jax.default_backend(),
        "windows": windows,
        "max_new": max_new,
    }
    _append_records(str(Path(__file__).parent.parent / "BENCH_asr.json"),
                    [record])
    print(json.dumps(record))
    assert int8_wps > 0 and bf16_wps > 0
    if on_tpu:
        assert ratio >= 1.2, record


# --------------------------------------------------------------------------
# Knob / doc agreement + pallasshim containment
# --------------------------------------------------------------------------

def test_raw_speed_knobs_parsed_and_documented():
    from vlog_tpu import config
    from vlog_tpu.analysis import registry as reg

    reg.assert_knobs(("VLOG_PALLAS", "VLOG_WHISPER_QUANT"))
    assert isinstance(config.PALLAS, str)
    assert isinstance(config.WHISPER_QUANT, str)
    # the compile cache is placed by jax's own variable, not a knob
    assert not hasattr(config, "COMPILE_CACHE_DIR")
    assert "VLOG_COMPILE_CACHE_DIR" not in (
        Path(__file__).parent.parent / "README.md").read_text()


def _fixture_pkg(tmp_path, files):
    pkg = tmp_path / "pkg"
    for rel, src in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return pkg


def test_pallasshim_every_raw_spelling_fires(tmp_path):
    from vlog_tpu.analysis import run_passes

    pkg = _fixture_pkg(tmp_path, {"worker/rogue.py": """\
        import jax
        import jax.experimental.pallas
        import jax.experimental.pallas.tpu
        from jax.experimental import pallas
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import pallas_call

        def kernel(x):
            return pl.pallas_call(lambda r, o: None)(x)

        def kernel2(x):
            return jax.experimental.pallas.pallas_call(lambda r, o: None)(x)
    """})
    found = run_passes(pkg, rules=["pallasshim"])
    msgs = [f.message for f in found]
    # 2 raw imports + 2 `from jax.experimental import pallas` + 1
    # `from ...pallas import` + 2 pallas_call attrs + 1 dotted attr
    assert len(msgs) == 8
    assert all("ops/pallas_ladder.py" in m for m in msgs)
    assert any("pallas_call attribute" in m for m in msgs)
    assert all(f.rule == "pallasshim" for f in found)


def test_pallasshim_shim_and_shim_users_are_clean(tmp_path):
    from vlog_tpu.analysis import run_passes

    pkg = _fixture_pkg(tmp_path, {
        # the kernel module itself may touch the raw API — that's its job
        "ops/pallas_ladder.py": """\
            from jax.experimental import pallas as pl

            def fused(x):
                return pl.pallas_call(lambda r, o: None)(x)
        """,
        # so may the transcript model's attention kernel (PR 34)
        "lm/attention_kernel.py": """\
            from jax.experimental import pallas as pl
            from jax.experimental.pallas import tpu as pltpu

            def chunk_attention(x):
                return pl.pallas_call(lambda r, o: None)(x)
        """,
        # sanctioned call sites import the shim, not jax
        "parallel/ladder.py": """\
            from pkg.ops.pallas_ladder import fused

            def program(x):
                return fused(x)
        """,
        # an attribute named pallas on a non-jax object is not the API
        "worker/ok.py": """\
            def run(backend):
                return backend.pallas(lambda x: x)
        """})
    assert run_passes(pkg, rules=["pallasshim"]) == []


def test_pallasshim_real_repo_is_clean():
    from vlog_tpu.analysis import default_pkg_dir, run_passes

    findings = [f for f in run_passes(default_pkg_dir())
                if f.rule == "pallasshim"]
    assert findings == []
