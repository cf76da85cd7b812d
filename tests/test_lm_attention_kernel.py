"""The kernel forms of ``paged_attention`` (lm/attention_kernel.py).

On the CPU a kernel runs in Pallas's interpreter at tiny widths and is
held to the loop form, which tests/test_lm_model.py holds to the plain
references: same blocks, same online softmax, so the two agree to
float32 rounding. One test compiles both families' ``lm_step_c2048`` at
the cells' shapes for a described v5e (no chip attached, nothing runs)
and reads the optimized HLO: the chunk's attention is a Mosaic custom
call under its layer's scope, the pools reach it without a copy, and no
float32 array of chunk x block keys x heads is left in the step; in
Keye's, the chunk's choice of keys is one custom call a layer between
the index scores and the attention's kernel, with no array of ordered
bits or boolean mask left; in every family's, the experts' rows are
summed back by one Mosaic custom call an expert layer, fed the down
product as it lies, with no second sort (``moe_kernel.py``), and that
kernel's module is one size at every bucket and ``k``. A second reads
Trinity's ``lm_step_c0`` and
``lm_step_c2048`` for the rows' kernel: one custom call a layer, no
``while`` left under the attention's scopes. The choice's kernel is
held to ``model.py::select_keys`` bit for bit, ties included.

The topology is described inside a fixture (the TPU's library belongs
to one process at a time; tests/test_beam_cache_layout.py and
tests/benchmark_checks/test_benchmark_sizes.py load it the same way).
"""

from __future__ import annotations

import functools
import json
import math
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vlog_tpu.lm import attention_kernel
from vlog_tpu.lm import model as lm_model

ROOT = Path(__file__).resolve().parents[1]
NKV, G, HD, PAGE, BP = 2, 2, 16, 4, 2
POOL, WIDTH = 48, 12           # pages in the pool, slots in the table
KEYS = WIDTH * PAGE


def _case(seed, nq, p0, n, window, base_pages, masked):
    """The arguments of one ``paged_attention`` call for a chunk of
    ``nq`` queries from ``p0`` of which ``n`` are real."""
    rng = np.random.default_rng(seed)
    pk, pv = (jnp.asarray(rng.normal(size=(POOL, PAGE, NKV, HD)),
                          jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(1, nq, NKV, G, HD)) * 0.4, jnp.bfloat16)
    table = np.zeros((1, WIDTH), np.int32)
    base = base_pages * PAGE
    live = (p0 + n - 1 - base) // PAGE + 1 if n else 0
    table[0, :live] = rng.permutation(np.arange(1, POOL))[:live]
    chosen = jnp.asarray(rng.random((1, nq, KEYS)) < 0.4) if masked else None
    qpos = (p0 + jnp.arange(nq, dtype=jnp.int32))[None]
    last = jnp.asarray([p0 + n - 1 if n else -1], jnp.int32)
    return (q, qpos, last, pk, pv, jnp.asarray(table),
            jnp.asarray([base], jnp.int32)), dict(
                window=window, page=PAGE, block_pages=BP, chosen=chosen)


def _both_forms(monkeypatch, args, kw, q_tile):
    """``paged_attention`` in the loop form, then in the kernel form
    (the interpreter in the kernel's place, ``q_tile`` queries a tile)."""
    assert lm_model.attention_form(1, args[0].shape[1], NKV, G, HD, PAGE,
                                   chosen=kw["chosen"] is not None) == "loop"
    loop = lm_model.paged_attention(*args, **kw)
    monkeypatch.setattr(lm_model, "attention_form", lambda *_, **__: "kernel")
    monkeypatch.setattr(
        attention_kernel, "chunk_attention", functools.partial(
            attention_kernel.chunk_attention, q_tile=q_tile, interpret=True))
    return loop, lm_model.paged_attention(*args, **kw)


def _same(loop, kernel):
    (want, want_pages), (got, got_pages) = loop, kernel
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert np.asarray(got_pages).tolist() == np.asarray(want_pages).tolist()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)
    return np.asarray(got)


MASKS = {"causal": (None, 0, False), "window": (8, 2, False),
         "chosen": (None, 0, True)}
# p0, n of a 16-query chunk: the whole bucket from a block's edge; a
# context that ends mid-page and mid-block; a chunk shorter than its
# bucket; an absent chunk
CHUNKS = {"aligned": (16, 16), "mid_page": (21, 16), "short": (21, 9),
          "absent": (21, 0)}


@pytest.mark.parametrize("chunk", CHUNKS)
@pytest.mark.parametrize("mask", MASKS)
def test_the_kernel_equals_the_loop(monkeypatch, mask, chunk):
    (window, base_pages, masked), (p0, n) = MASKS[mask], CHUNKS[chunk]
    args, kw = _case(3, 16, p0, n, window, base_pages, masked)
    got = _same(*_both_forms(monkeypatch, args, kw, q_tile=8))
    if n:
        assert np.abs(got[0, :n]).max() > 0.05      # it attended something
    else:
        assert not got.any()                        # absent: zeros


@pytest.mark.parametrize("tiles", [2, 4, 8, 16])
@pytest.mark.parametrize("mask", MASKS)
def test_every_bucket_over_the_tile(monkeypatch, mask, tiles):
    """The cells' buckets are 2, 4, 8 and 16 query tiles (256 to 2,048
    over 128); a tile's own block range differs with its place."""
    window, base_pages, masked = MASKS[mask]
    nq = 2 * tiles
    args, kw = _case(tiles, nq, 45 - nq, nq - 1, window,
                     base_pages and (45 - nq - 7) // PAGE, masked)
    _same(*_both_forms(monkeypatch, args, kw, q_tile=2))


def test_a_query_no_key_is_chosen_for_reads_zeros(monkeypatch):
    args, kw = _case(5, 16, 8, 16, None, 0, True)
    kw["chosen"] = kw["chosen"].at[0, 3].set(False).at[0, 11].set(False)
    got = _same(*_both_forms(monkeypatch, args, kw, q_tile=8))
    assert not got[0, 3].any() and not got[0, 11].any() and got[0, 4].any()


# --------------------------------------------------------------------------
# the rows: one query a sequence, each over its own pages
# --------------------------------------------------------------------------

def _kv_rows_case(seed, lasts, nkv, g, window, ahead=0):
    """The arguments of one ``paged_attention`` call for one query a
    sequence at the positions ``lasts`` (-1: the sequence is absent). A
    window row's table is a ring: it starts at the page its band starts
    in, less ``ahead`` pages the ring has not yet given back."""
    rng = np.random.default_rng(seed)
    pk, pv = (jnp.asarray(rng.normal(size=(POOL, PAGE, nkv, HD)),
                          jnp.bfloat16) for _ in range(2))
    rows = len(lasts)
    q = jnp.asarray(rng.normal(size=(rows, 1, nkv, g, HD)) * 0.4,
                    jnp.bfloat16)
    table = np.zeros((rows, WIDTH), np.int32)
    base = np.zeros(rows, np.int32)
    for i, last in enumerate(lasts):
        if last < 0:
            continue
        if window is not None:
            base[i] = max((last - window + 1) // PAGE - ahead, 0) * PAGE
        live = (last - base[i]) // PAGE + 1
        table[i, :live] = rng.permutation(np.arange(1, POOL))[:live]
    last = jnp.asarray(lasts, jnp.int32)
    return (q, jnp.maximum(last, 0)[:, None], last, pk, pv,
            jnp.asarray(table), jnp.asarray(base)), dict(
                window=window, page=PAGE, block_pages=BP)


# one key; a context that ends mid-page (13) and one that ends mid-block
# (21: the third block's first page); the longest; an absent row
RAGGED = (0, 13, -1, 21, 47, 30)
KV_ROWS = {
    "full": (RAGGED, 2, 2, None, 0),
    "full_nkv4": (RAGGED, 4, 2, None, 0),
    "full_g4": (RAGGED, 2, 4, None, 0),
    "full_longest": ((47, 47, 47), 2, 2, None, 0),
    "full_absent": ((-1, -1), 2, 2, None, 0),
    "full_page_edges": ((3, 4, 7, 8, 15, 16), 2, 2, None, 0),
    # window 10 over pages of 4: the band starts mid-page; rows still
    # inside their first window (base 0) beside rows whose ring has moved
    "window": ((0, 5, 9, 13, -1, 30, 47, 95), 2, 2, 10, 0),
    "window_nkv4": ((0, 5, 9, 13, -1, 30, 47, 95), 4, 2, 10, 0),
    # the ring still holds pages behind the band: the row's first blocks
    # are out of sight and are skipped
    "window_pages_behind": ((30, 47, 95, 22, 41), 2, 2, 10, 5),
    "window_whole_pages": ((7, 8, 11, 12, 40), 2, 2, 8, 1),
}


@pytest.mark.parametrize("case", KV_ROWS)
def test_the_kv_rows_kernel_equals_the_loop(monkeypatch, case):
    """Rows of ragged length in one call: each reads its own pages, in
    the loop's blocks, and comes out as the loop gives it."""
    lasts, nkv, g, window, ahead = KV_ROWS[case]
    args, kw = _kv_rows_case(19, lasts, nkv, g, window, ahead)
    assert lm_model.attention_form(len(lasts), 1, nkv, g, HD,
                                   PAGE) == "loop"
    loop = lm_model.paged_attention(*args, **kw)
    monkeypatch.setattr(lm_model, "attention_form",
                        lambda *_, **__: "rows_kernel")
    monkeypatch.setattr(
        attention_kernel, "rows_attention", functools.partial(
            attention_kernel.rows_attention, interpret=True))
    got = _same(loop, lm_model.paged_attention(*args, **kw))
    assert got.shape == (len(lasts), 1, nkv, g, HD)
    for i, last in enumerate(lasts):
        assert got[i].any() == (last >= 0)          # absent: zeros
        if last >= 0:
            assert np.abs(got[i]).max() > 0.05      # it attended something


def test_the_grid_is_the_blocks_the_rows_read_and_no_more():
    """One grid step a block that some row reads: a step of the engine
    costs the SUM of its rows' visible blocks, not rows x the longest."""
    plan = functools.partial(attention_kernel._rows_plan, width=WIDTH,
                             page=PAGE, bp=BP)
    # a full row of 48 keys, one of 14, an absent one, one of 2
    last = jnp.asarray([47, 13, -1, 1], jnp.int32)
    steps = plan(jnp.maximum(last, 0), last, jnp.zeros(4, jnp.int32),
                 window=None)
    t = 4 * WIDTH // BP
    row, block, top, total = (steps[:t], steps[t:2 * t], steps[2 * t:3 * t],
                              int(steps[3 * t]))
    # 6 blocks, 2 blocks, the absent row's one step (zeros), 1 block
    assert total == 6 + 2 + 1 + 1
    assert row[:total].tolist() == [0] * 6 + [1] * 2 + [2] + [3]
    assert block[:total].tolist() == [0, 1, 2, 3, 4, 5, 0, 1, 0, 0]
    # 14 keys end in the fourth page, 2 in the first: slots stop there
    assert top[:total].tolist() == [11] * 6 + [3] * 2 + [0] + [0]
    assert set(row[total:].tolist()) == {3}         # the rest repeat
    # a window row (10) at 95 whose ring starts at page 20: its band
    # starts in slot 1 and ends in slot 3, so it reads blocks 0 and 1; one
    # whose ring still holds five pages behind the band (slots 5 to 7 in
    # sight) reads blocks 2 and 3 and never the two before them
    steps = plan(jnp.asarray([95, 95]), jnp.asarray([95, 95]),
                 jnp.asarray([80, 64]), window=10)
    t = 2 * WIDTH // BP
    assert int(steps[3 * t]) == 2 + 2
    assert steps[t:t + 4].tolist() == [0, 1, 2, 3]
    assert steps[2 * t:2 * t + 4].tolist() == [3, 3, 7, 7]


def test_the_form_is_read_from_the_call(monkeypatch):
    """A chunk on a TPU takes the kernel where Mosaic tiles its shapes;
    several sequences of several queries, any other backend and shapes
    the kernel does not tile take the loop."""
    # (sequences, queries, nkv, g, hd, page)
    form = lm_model.attention_form
    assert form(1, 2048, 4, 8, 128, 256) == "loop"             # the CPU
    monkeypatch.setattr(lm_model.jax, "default_backend", lambda: "tpu")
    assert form(1, 2048, 4, 8, 128, 256) == "kernel"
    assert form(1, 256, 4, 8, 128, 256) == "kernel"
    assert form(1, 2048, 4, 8, 128, 256, chosen=True) == "kernel"
    assert form(1, 2048, 4, 8, 128, 256, expand=True) == "loop"
    assert form(2, 2048, 4, 8, 128, 256) == "loop"
    assert form(1, 16, 2, 2, 16, 4) == "loop"
    assert form(1, 2048, 3, 8, 128, 256) == "loop"


def test_the_rows_form_of_kv_pages_is_read_from_the_call(monkeypatch):
    """Rows of one query each take the rows' kernel on a TPU, without a
    choice of keys and over K/V pages alone: a pool that ``expand``
    reads (every ``keys_minor`` caller) and any other backend take the
    loop, as do shapes Mosaic does not tile."""
    form = lm_model.attention_form
    assert form(32, 1, 4, 8, 128, 256) == "loop"               # the CPU
    monkeypatch.setattr(lm_model.jax, "default_backend", lambda: "tpu")
    assert form(32, 1, 4, 8, 128, 256) == "rows_kernel"
    assert form(16, 1, 4, 8, 128, 256) == "rows_kernel"
    assert form(1, 1, 4, 8, 128, 256) == "rows_kernel"
    assert form(32, 1, 4, 8, 128, 256, chosen=True) == "loop"
    assert form(32, 1, 1, 32, 576, 256, expand=True) == "loop"  # latents
    assert form(32, 1, 4, 8, 64, 256) == "loop"
    assert form(32, 1, 4, 8, 128, 64) == "loop"
    assert form(32, 1, 2, 2, 128, 256) == "loop"
    assert form(5, 1, NKV, G, HD, PAGE) == "loop"


# --------------------------------------------------------------------------
# the chunk's choice of keys: the kernel against select_keys, bit for bit
# --------------------------------------------------------------------------

def _choice_case(seed, nq, width, p0, n, *, ties=False, tie_rows=None):
    """Index scores of a chunk of ``nq`` queries from ``p0`` of which
    ``n`` are real (``n_keys = p0 + n``), as ``index_scores`` leaves them:
    ``-inf`` past a query's position and past ``n_keys``, never -0.0.
    With ``ties`` the scores take a few values, in ``tie_rows`` alone
    where given."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(nq, width)).astype(np.float32)
    if ties:
        rows = slice(None) if tie_rows is None else tie_rows
        x[rows] = np.round(x[rows] * 2) / 2
        x[x == 0.0] = 0.0
    qpos = p0 + np.arange(nq)
    col = np.arange(width)[None, :]
    x[(col > qpos[:, None]) | (col >= p0 + n)] = -np.inf
    return x, p0 + n


def _straddles(x, top):
    """Per query: do ties at its ``top``-th best score straddle the cut?"""
    out = []
    for row in x:
        keys = row[np.isfinite(row)]
        if keys.size <= top:
            out.append(False)
            continue
        kth = np.sort(keys)[::-1][top - 1]
        out.append((keys > kth).sum() < top < (keys >= kth).sum())
    return np.asarray(out)


def _both_choices(x, top, p0, n_keys, *, block, q_tile):
    """``select_keys`` (the loop) and the kernel in the interpreter."""
    want = np.asarray(lm_model.select_keys(
        jnp.asarray(x)[None], top, jnp.int32(n_keys), block=block))[0]
    got = np.asarray(attention_kernel.select_keys_kernel(
        jnp.asarray(x), top, jnp.int32(p0), jnp.int32(n_keys), block=block,
        q_tile=q_tile, interpret=True))
    assert got.dtype == np.int8 and set(np.unique(got)) <= {0, 1}
    assert (got.astype(bool) == want).all()
    return got.astype(bool)


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("top", [1, 4, 16, 41, 64])
def test_the_choice_kernel_equals_the_loop(top, ties):
    """Every ``top`` of ``test_the_chosen_set_is_lax_top_ks``, with and
    without ties: 64 queries from position 20 over 80 keys, so the first
    rows hold fewer keys than the larger ``top`` (all of them chosen)
    and the last more; ``n_keys`` ends inside the first block."""
    x, n_keys = _choice_case(top, 64, 512, 20, 60, ties=ties)
    got = _both_choices(x, top, 20, n_keys, block=128, q_tile=32)
    assert (got.sum(1) == np.minimum(top, np.isfinite(x).sum(1))).all()
    if ties and top > 1:
        assert _straddles(x, top).any()


CHOICES = {
    # (seed, nq, width, p0, n, q_tile, ties, tie rows, top)
    # a row of nothing but equal scores beside rows of ties
    "all_ties": (1, 64, 512, 150, 64, 32, True, None, 16),
    # eight tiles of 32 whose causal ends fall in the first, second and
    # third block of 128; n_keys (308) not a multiple of the block, the
    # last tile's last queries padding, the fourth block past every tile
    "tiles_in_blocks": (2, 256, 512, 60, 248, 32, False, None, 24),
    # a short chunk: queries past n are padding, n_keys ends mid-block
    "short_chunk": (3, 64, 1024, 300, 37, 32, True, None, 50),
    # two tiles of 64 (two row groups each): rows of a chunk's very start
    # (fewer keys than top) beside rows that hold more
    "rows_under_top": (4, 128, 512, 0, 128, 64, False, None, 96),
}


@pytest.mark.parametrize("case", CHOICES)
def test_the_choice_kernel_over_tiles_and_blocks(case):
    """The kernel's tiles each read their own causal blocks: what lies
    past a tile's last block, past a query's position and past
    ``n_keys`` reads 0, and what is chosen is ``select_keys``' set."""
    seed, nq, width, p0, n, tq, ties, tie_rows, top = CHOICES[case]
    x, n_keys = _choice_case(seed, nq, width, p0, n, ties=ties,
                             tie_rows=tie_rows)
    if case == "all_ties":
        x[0, np.isfinite(x[0])] = 0.5
    got = _both_choices(x, top, p0, n_keys, block=128, q_tile=tq)
    qpos = p0 + np.arange(nq)
    col = np.arange(width)[None, :]
    assert not got[(col > qpos[:, None]) | (col >= n_keys)].any()
    assert (got.sum(1) == np.minimum(top, np.isfinite(x).sum(1))).all()
    if case == "all_ties":          # the first ``top`` positions win
        assert got[0].nonzero()[0].tolist() == list(range(top))


def test_a_tie_straddles_the_cut_in_one_tile_and_not_the_next():
    """Ties in the first tile's rows alone: the bisection over positions
    runs there and not in the second tile, and both come out as the
    loop's."""
    x, n_keys = _choice_case(5, 64, 512, 200, 64, ties=True,
                             tie_rows=slice(0, 32))
    straddles = _straddles(x, 40)
    assert straddles[:32].any() and not straddles[32:].any()
    _both_choices(x, 40, 200, n_keys, block=128, q_tile=32)


def test_the_choice_form_is_read_from_the_call(monkeypatch):
    """A chunk on a TPU chooses in the kernel where Mosaic tiles its
    shapes and a tile's row fits VMEM; any other backend, and shapes
    the kernel does not tile, take ``select_keys``."""
    form = lm_model.select_form
    assert form(2048, 40960, 1024) == "loop"                   # the CPU
    monkeypatch.setattr(lm_model.jax, "default_backend", lambda: "tpu")
    assert form(2048, 40960, 1024) == "kernel"
    assert form(256, 40960, 1024) == "kernel"
    assert form(2048, 4096, 512) == "kernel"
    assert form(16, 64, 8) == "loop"                # the tests' tiny model
    assert form(48, 40960, 1024) == "loop"          # no whole int8 tile
    assert form(2048, 40960 + 512, 1024) == "loop"  # not whole blocks
    assert form(2048, 40960, 1000) == "loop"        # not whole lane blocks
    assert form(2048, 1 << 20, 1024) == "loop"      # a row past VMEM


# --------------------------------------------------------------------------
# what the TPU compiler makes of the cells' chunk step
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile_step(monkeypatch, one_chip, config_name, weights, chunk):
    """``(cfg, geo, step, compiled)``: the cell's ``lm_step_c<chunk>``
    compiled at its own shapes for the described chip."""
    if str(ROOT / "benchmark") not in sys.path:
        sys.path.insert(0, str(ROOT / "benchmark"))
    make_params = __import__(f"models.{weights}",
                             fromlist=["make_params"]).make_params
    cfgd = json.loads(
        (ROOT / "benchmark" / "configs" / f"{config_name}.json").read_text())
    cfg, dep = lm_model.LmConfig.from_hf(cfgd), cfgd["deployment"]
    geo = lm_model.Geometry(**{k: int(dep[k]) for k in (
        "rows", "chunk", "page", "context_cap", "kv_block_pages",
        "window_pages", "full_pages")})
    chunk = geo.chunk if chunk is None else chunk

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    # the described chip is not the process's backend: steer the one
    # question the program asks of it
    monkeypatch.setattr(lm_model.jax, "default_backend", lambda: "tpu")
    step = lm_model.build_step(cfg, geo, chunk)
    plan = {k: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for k, (s, d) in lm_model.plan_shapes(cfg, geo, chunk).items()}
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        described(jax.eval_shape(lambda: make_params(cfgd, 7))),
        described(jax.eval_shape(lambda: lm_model.empty_cache(cfg, geo))),
        jax.ShapeDtypeStruct((geo.rows,), jnp.int32, sharding=one_chip),
        plan).compile()
    return cfg, geo, step, compiled


def _custom_calls(text, name):
    return [ln for ln in text.splitlines()
            if " custom-call(" in ln and name in ln]


def _combines_in_the_kernel(cfg, step, text):
    """The experts' rows are summed back by one Mosaic custom call an
    expert layer under ``lm.moe.combine``, reading the down product as
    it lies (a bitcast, never a copy), with no second sort there."""
    assert step.moe_combine_form == "kernel"
    calls = _custom_calls(text, "lm_moe_combine")
    assert len(calls) == cfg.num_layers - cfg.num_dense_layers
    assert all('custom_call_target="tpu_custom_call"' in ln
               and "/lm.moe.combine/lm_moe_combine" in ln for ln in calls)
    assert all(" bitcast(" in _operands(text, ln)[-1] for ln in calls)
    assert not [ln for ln in text.splitlines()
                if " sort(" in ln and "lm.moe.combine" in ln]


def _operands(text, call):
    """The HLO lines that define a custom call's operands, in order."""
    names = {ln.strip().split(" = ", 1)[0].removeprefix("ROOT ").lstrip("%"):
             ln for ln in text.splitlines() if " = " in ln}
    inside = re.search(r"custom-call\(([^)]*)\)", call).group(1)
    return [names[o.strip().lstrip("%")] for o in
            re.sub(r"/\*[^*]*\*/", "", inside).split(",")]


@pytest.mark.parametrize("config_name,weights", [
    ("trinity_mini_6l", "afmoe_weights"), ("keye_vl2_lm_6l", "keye_weights")])
def test_the_cells_chunk_step_compiles_to_the_kernel(
        monkeypatch, one_chip, no_compile_cache, config_name, weights):
    cfg, geo, step, compiled = _compile_step(monkeypatch, one_chip,
                                             config_name, weights, None)
    assert step.attn_chunk_form == "kernel"
    assert step.attn_rows_form == (
        "gathered" if cfg.index_topk else "rows_kernel")
    text = compiled.as_text()

    calls = _custom_calls(text, "lm_chunk_attention")
    assert len(calls) == cfg.num_layers
    assert all('custom_call_target="tpu_custom_call"' in ln for ln in calls)
    scopes = {"lm.attn.sparse": cfg.num_layers} if cfg.index_topk else {
        "lm.attn.window": cfg.window_layers, "lm.attn.full": cfg.full_layers}
    assert {s: sum(f"/{s}/lm_chunk_attention" in ln for ln in calls)
            for s in scopes} == scopes
    # the pools reach the kernel as they lie: a bitcast, never a copy
    for ln in calls:
        pools = _operands(text, ln)[4:12]
        assert all(" bitcast(" in o for o in pools), pools
    # a block's scores (chunk x block keys x heads, float32) stay on the
    # chip: the loop form leaves f32[1,4,8,2048,1024] in the step
    scores = geo.chunk * geo.kv_block_pages * geo.page \
        * cfg.num_attention_heads
    left = {m.group(0) for m in re.finditer(r"f32\[([0-9,]+)\]", text)
            if math.prod(int(d) for d in m.group(1).split(",")) == scores}
    assert not left

    # the chunk's choice of keys: one kernel a layer, fed the index
    # scores as they lie and feeding the attention's kernel its mask; no
    # array of ordered bits and no boolean mask is left to relay
    assert step.attn_select_form == ("kernel" if cfg.index_topk else None)
    choices = [ln for ln in _custom_calls(text, "lm_select_keys")
               if "/lm.attn.select/lm_select_keys" in ln]
    assert len(choices) == (cfg.num_layers if cfg.index_topk else 0)
    for ln in choices:
        assert 'custom_call_target="tpu_custom_call"' in ln
        assert " bitcast(" in _operands(text, ln)[-1]
    for ln in calls if cfg.index_topk else ():
        mask = _operands(text, ln)[-1]
        assert "= s8[" in mask and " custom-call(" in mask \
            and "/lm_select_keys" in mask
    row = f"{geo.chunk},{geo.key_width}]"
    assert not [ln for ln in text.splitlines()
                if f"u32[1,{row}" in ln or f"pred[{row}" in ln]
    _combines_in_the_kernel(cfg, step, text)


@pytest.mark.parametrize("chunk", [0, 2048])
def test_trinitys_rows_compile_to_the_kernel(
        monkeypatch, one_chip, no_compile_cache, chunk):
    """Every bucket's program (the decode-only one and the fullest
    shown) holds one Mosaic custom call a layer for the rows, fed by the
    pools as they lie, and no loop is left under the attention."""
    cfg, geo, step, compiled = _compile_step(
        monkeypatch, one_chip, "trinity_mini_6l", "afmoe_weights", chunk)
    assert step.attn_rows_form == "rows_kernel"
    assert step.attn_chunk_form == ("kernel" if chunk else None)
    text = compiled.as_text()
    calls = _custom_calls(text, "lm_rows_attention")
    assert len(calls) == cfg.num_layers == 6
    assert all('custom_call_target="tpu_custom_call"' in ln for ln in calls)
    assert {s: sum(f"/{s}/lm_rows_attention" in ln for ln in calls)
            for s in ("lm.attn.window", "lm.attn.full")} == {
                "lm.attn.window": cfg.window_layers,
                "lm.attn.full": cfg.full_layers}
    for ln in calls:
        # last, after the grid's plan and q: a block's pages of K and V
        pools = _operands(text, ln)[-2 * geo.kv_block_pages:]
        assert all(" bitcast(" in o for o in pools), pools
    # the rows' fori_loop is gone: no while under the attention's scopes,
    # and no block of gathered K/V (rows x block keys x kv heads x 128)
    assert not [ln for ln in text.splitlines() if " while(" in ln
                and re.search(r"lm\.attn\.(window|full)", ln)]
    gathered = "bf16[%d,%d,%d,%d]" % (
        geo.rows, geo.kv_block_pages * geo.page, cfg.num_key_value_heads,
        cfg.head_dim)
    assert gathered not in text


# --------------------------------------------------------------------------
# latent attention's chunk: the expanded form as a kernel
# --------------------------------------------------------------------------

L_HEADS, L_NOPE, L_ROPE, L_RANK, L_V = 4, 16, 8, 16, 16


def _latent_case(seed, nq, p0, n):
    """``expanded_attention``'s arguments for a chunk of ``nq`` queries
    from ``p0`` of which ``n`` are real, over a pool of latents with the
    positions in the lanes."""
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(POOL, L_RANK + L_ROPE, PAGE)),
                       jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(nq, L_HEADS, L_NOPE + L_ROPE)),
                    jnp.float32)
    w = jnp.asarray(rng.normal(size=(L_RANK, L_HEADS, L_NOPE + L_V)) * 0.3,
                    jnp.bfloat16)
    table = np.zeros(WIDTH, np.int32)
    live = (p0 + n - 1) // PAGE + 1 if n else 0
    table[:live] = rng.permutation(np.arange(1, POOL))[:live]
    return (q, jnp.int32(p0), jnp.int32(p0 + n - 1 if n else -1), pool,
            jnp.asarray(table), w), dict(nope=L_NOPE, scale=0.2, page=PAGE,
                                         block_pages=BP)


def _latent_forms(monkeypatch, args, kw, q_tile):
    assert lm_model.latent_chunk_form(
        args[0].shape[0], L_NOPE, L_ROPE, L_RANK, L_V, PAGE) \
        == "latent_expanded_loop"
    loop = lm_model.expanded_attention(*args, **kw)
    monkeypatch.setattr(lm_model, "latent_chunk_form",
                        lambda *_: "latent_expanded_kernel")
    monkeypatch.setattr(
        attention_kernel, "latent_chunk_attention", functools.partial(
            attention_kernel.latent_chunk_attention, q_tile=q_tile,
            interpret=True))
    return loop, lm_model.expanded_attention(*args, **kw)


@pytest.mark.parametrize("q_tile", [4, 16])
@pytest.mark.parametrize("chunk", CHUNKS)
def test_the_latent_kernel_equals_the_loop(monkeypatch, chunk, q_tile):
    p0, n = CHUNKS[chunk]
    args, kw = _latent_case(11, 16, p0, n)
    loop, kernel = _latent_forms(monkeypatch, args, kw, q_tile)
    assert kernel.shape == loop.shape == (16, L_HEADS, L_V)
    assert kernel.dtype == jnp.float32
    # the rows past the chunk's real queries attend the keys they see in
    # both forms; an absent chunk reads zeros
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(loop),
                               rtol=2e-6, atol=2e-6)
    if not n:
        assert not np.asarray(kernel).any()


@pytest.mark.parametrize("tiles", [2, 8])
def test_every_latent_bucket_over_the_sub_tile(monkeypatch, tiles):
    args, kw = _latent_case(13, 4 * tiles, 9, 4 * tiles - 3)
    loop, kernel = _latent_forms(monkeypatch, args, kw, 4)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(loop),
                               rtol=2e-6, atol=2e-6)


def test_the_latent_form_is_read_from_the_call(monkeypatch):
    # the cell's shapes: a kernel on a TPU, the loop anywhere else
    shapes = (128, 64, 512, 128, 256)
    assert lm_model.latent_chunk_form(2048, *shapes) == "latent_expanded_loop"
    monkeypatch.setattr(lm_model.jax, "default_backend", lambda: "tpu")
    for nq in (256, 512, 1024, 2048):
        assert lm_model.latent_chunk_form(nq, *shapes) \
            == "latent_expanded_kernel"
    # the tiny model's heads are no lane blocks: the loop on any backend
    assert lm_model.latent_chunk_form(16, L_NOPE, L_ROPE, L_RANK, L_V,
                                      PAGE) == "latent_expanded_loop"


def _rows_case(seed, lasts):
    """``absorbed_attention``'s arguments for one query a sequence at
    the positions ``lasts`` (-1: the sequence is absent)."""
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(POOL, L_RANK + L_ROPE, PAGE)),
                       jnp.bfloat16)
    rows = len(lasts)
    q = jnp.asarray(rng.normal(size=(rows, L_HEADS, L_NOPE + L_ROPE)),
                    jnp.float32)
    w = jnp.asarray(rng.normal(size=(L_RANK, L_HEADS, L_NOPE + L_V)) * 0.3,
                    jnp.bfloat16)
    table = np.zeros((rows, WIDTH), np.int32)
    for i, last in enumerate(lasts):
        live = last // PAGE + 1 if last >= 0 else 0
        table[i, :live] = rng.permutation(np.arange(1, POOL))[:live]
    last = jnp.asarray(lasts, jnp.int32)
    return (q, last, last, pool, jnp.asarray(table), w), dict(
        nope=L_NOPE, scale=0.2, page=PAGE, block_pages=BP)


@pytest.mark.parametrize("lasts", [
    (0, 13, -1, 30, 47), (47, 47, 47), (-1, -1), (3, 4, 7, 8)],
    ids=["mixed", "longest", "absent", "page_edges"])
def test_the_rows_kernel_equals_the_loop(monkeypatch, lasts):
    """Rows of one page and of twelve, a row that is absent, rows at a
    page's and a block's edge: each reads its own pages and no more."""
    args, kw = _rows_case(17, lasts)
    assert lm_model.latent_rows_form(L_HEADS, L_RANK + L_ROPE,
                                     PAGE) == "loop"
    loop = lm_model.absorbed_attention(*args, **kw)
    monkeypatch.setattr(lm_model, "latent_rows_form", lambda *_: "kernel")
    monkeypatch.setattr(
        attention_kernel, "latent_rows_attention", functools.partial(
            attention_kernel.latent_rows_attention, interpret=True))
    kernel = lm_model.absorbed_attention(*args, **kw)
    assert kernel.shape == loop.shape == (len(lasts), L_HEADS, L_V)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(loop),
                               rtol=2e-6, atol=2e-6)
    for i, last in enumerate(lasts):
        assert np.asarray(kernel[i]).any() == (last >= 0)


def test_the_rows_form_is_read_from_the_call(monkeypatch):
    assert lm_model.latent_rows_form(32, 576, 256) == "loop"
    monkeypatch.setattr(lm_model.jax, "default_backend", lambda: "tpu")
    assert lm_model.latent_rows_form(32, 576, 256) == "kernel"
    assert lm_model.latent_rows_form(L_HEADS, L_RANK + L_ROPE,
                                     PAGE) == "loop"


def test_the_chapters_cells_chunk_step_compiles_to_the_latent_kernel(
        monkeypatch, one_chip, no_compile_cache):
    cfg, geo, step, compiled = _compile_step(
        monkeypatch, one_chip, "xing4_29b_6l", "xing_weights", None)
    assert step.attn_chunk_form == "latent_expanded_kernel"
    assert step.attn_rows_form == "latent_absorbed"
    text = compiled.as_text()
    calls = _custom_calls(text, "lm_latent_chunk_attention")
    assert len(calls) == cfg.num_layers == 6
    assert all('custom_call_target="tpu_custom_call"' in ln for ln in calls)
    assert all("/lm.attn.latent.chunk/lm_latent_chunk_attention" in ln
               for ln in calls)
    rows = _custom_calls(text, "lm_latent_rows_attention")
    assert len(rows) == cfg.num_layers
    assert all("/lm.attn.latent.rows/lm_latent_rows_attention" in ln
               for ln in rows)
    calls += rows
    # the pool reaches the kernel as it lies: a bitcast, never a copy,
    # and nowhere in the step is a whole pool relaid (880 MB a layer)
    for ln in calls:
        pools = _operands(text, ln)[-geo.kv_block_pages:]
        assert all(" bitcast(" in o for o in pools), pools
    pages = geo.full_pages
    assert not [ln for ln in text.splitlines()
                if re.search(rf"= bf16\[{pages},[0-9,]+\][^ ]* (copy|transpose)"
                             r"\(", ln)]
    # a block's scores and its expanded keys stay on the chip
    scores = geo.chunk * geo.kv_block_pages * geo.page \
        * cfg.num_attention_heads
    left = {m.group(0) for m in re.finditer(r"f32\[([0-9,]+)\]", text)
            if math.prod(int(d) for d in m.group(1).split(",")) == scores}
    assert not left
    # weights, pool and a chunk step's temporaries fit the chip
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9
    _combines_in_the_kernel(cfg, step, text)


def test_the_combine_kernel_lowers_to_one_size_at_every_bucket(
        one_chip, no_compile_cache):
    """The combine's Mosaic module (the custom call's serialized body)
    is the same size at 256 tokens as at 2,080 and at ``k`` 4, 8 or 10:
    every loop of its body over tokens, pairs or ``k`` is a loop in the
    module, not unrolled by Python. Tracing and lowering it then cost the
    same at every bucket, once a shape (``moe.combine`` is one jit)."""
    from vlog_tpu.lm import moe

    def body(t, k):
        def shape(s, d):
            return jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        text = moe.combine.lower(
            shape((t * k, 2048), jnp.float32), shape((t * k,), jnp.int32),
            shape((t, k), jnp.float32), None, form="kernel").as_text()
        calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
        assert len(calls) == 1
        return len(re.search(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)",
                             calls[0]).group(1))

    # the shapes' own digits and types move it a few hundred bytes; one
    # DMA start written out a pair at a time adds some 2 KB a pair
    sizes = [body(t, k) for t in (256, 2080) for k in (4, 8, 10)]
    assert max(sizes) <= 1.1 * min(sizes) and max(sizes) < 32 << 10, sizes
