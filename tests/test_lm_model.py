"""The transcript model at tiny widths on the CPU (window 16, page 4,
8 experts top 2, 2 dense + 4 expert layers, vocabulary 512): prefill in
chunks then decoding through the paged cache against the plain
reference's full forward pass."""

import numpy as np
import pytest

from lm_helpers import LOGIT_TOL, compare, engine, ref, tiny

# (prompt, output): under a page, across pages, across a chunk, across
# the window, a whole number of chunks, many decode steps past the window
LENGTHS = [(3, 2), (5, 6), (8, 3), (23, 9), (41, 12), (64, 5), (17, 30)]


@pytest.fixture(scope="module")
def served():
    hf, cfg, params = tiny()
    eng = engine(cfg, params)
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n), max_new=new,
                       capture=tuple(range(new)))
            for n, new in LENGTHS]
    for r in reqs:
        r.wait(300)
    log = list(eng.step_log)
    eng.close()
    return hf, cfg, params, reqs, log


@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_paged_prefill_and_decode_match_the_reference(served, i):
    hf, _cfg, params, reqs, _log = served
    req = reqs[i]
    assert len(req.tokens) == LENGTHS[i][1]
    errs, gaps = compare(req, hf, params)
    assert len(errs) >= max(1, len(req.tokens) // 2)
    assert max(errs) < LOGIT_TOL
    assert max(gaps) < 0.05


@pytest.mark.parametrize("mechanism", ref.MECHANISMS)
def test_leaving_a_mechanism_out_fails_the_comparison(served, mechanism):
    hf, _cfg, params, reqs, _log = served
    req = reqs[4]                       # 41 + 12: chunks, window, decode
    errs, _gaps = compare(req, hf, params, off=(mechanism,))
    assert max(errs) > 2 * LOGIT_TOL, mechanism


def test_the_reference_knows_its_mechanisms(served):
    hf, _cfg, params, reqs, _log = served
    with pytest.raises(ValueError):
        ref.forward(params, hf, reqs[0].prompt, [0], off=("no_such",))


def test_window_layers_visit_only_their_band(served):
    _hf, cfg, _params, _reqs, log = served
    seen = sum(r["window_pages"][0] for r in log)
    would = sum(r["window_pages"][1] for r in log)
    assert 0 < seen < would
    # a decoding row far past the window reads at most window/page + 1
    long_rows = [r for r in log if r["decode_rows"] == 1
                 and r["prefill_tokens"] == 0]
    assert long_rows and all(
        r["window_pages"][0] <= cfg.sliding_window // 4 + 1
        for r in long_rows)


def test_every_token_to_the_same_experts_matches_the_reference():
    hf, cfg, params = tiny()
    for lp in params["layers"]:
        if "bias" in lp:        # experts 0 and 1 win every token
            lp["bias"] = lp["bias"].at[0].set(10.0).at[1].set(5.0)
    eng = engine(cfg, params)
    try:
        req = eng.submit(np.arange(30) % cfg.vocab_size, max_new=6,
                         capture=tuple(range(6)))
        req.wait(300)
        loads = [r["expert_load"] for r in eng.step_log]
    finally:
        eng.close()
    # the fullest expert holds half of a step's (token, choice) pairs
    assert all(mx * 2 == total and held == 2
               for step in loads for mx, total, held in step)
    errs, gaps = compare(req, hf, params)
    assert len(errs) == 6 and max(errs) < LOGIT_TOL and max(gaps) < 0.05


def test_alone_and_packed_give_the_same_logits():
    _hf, cfg, params = tiny()
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 37)
    eng = engine(cfg, params, rows=8)
    try:
        alone = eng.submit(prompt, max_new=8, capture=tuple(range(8)))
        alone.wait(300)
        others = [eng.submit(rng.integers(0, cfg.vocab_size, 5 + 9 * i),
                             max_new=10 + i) for i in range(4)]
        packed = eng.submit(prompt, max_new=8, capture=tuple(range(8)))
        others += [eng.submit(rng.integers(0, cfg.vocab_size, 12 + 7 * i),
                              max_new=12) for i in range(3)]
        for r in [packed, *others]:
            r.wait(300)
        beside = max(r["decode_rows"] for r in eng.step_log
                     if packed.tag in r["emitted"])
    finally:
        eng.close()
    assert beside >= 4                  # it did decode beside others
    assert alone.tokens == packed.tokens
    for i in range(8):
        assert ref.logit_error(packed.logits[i], alone.logits[i]) < 0.02


# ---- KeyeVL2: learned sparse attention ----------------------------------
# (4 index heads of 8, 16 keys kept, page 4, blocks of 2 pages, chunks of
# 16: a selection of 16 crosses pages, blocks and chunks from position 17
# on)

from lm_helpers import (compare_keye, geometry, keye_ref,  # noqa: E402
                        tiny_keye)

from vlog_tpu.lm import model as lm_model  # noqa: E402
from vlog_tpu.lm import moe as lm_moe  # noqa: E402
from vlog_tpu.lm.cache import PagedCache  # noqa: E402
from vlog_tpu.lm.model import (Geometry, LmConfig, empty_cache,  # noqa: E402
                               plan_shapes)

# (prompt, output): under the selection, ending AT it (16 keys at the
# last step), one over, a chunk and a block boundary inside the
# selection, many chunks, decoding far past it
KEYE_LENGTHS = [(3, 2), (12, 5), (15, 4), (16, 6), (33, 9), (64, 5),
                (30, 30), (100, 8)]


def _serve_keye(params, cfg, lengths, seed=3, **geo):
    eng = engine(cfg, params, chunk=16, **geo)
    rng = np.random.default_rng(seed)
    try:
        reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n), max_new=new,
                           capture=tuple(range(new)))
                for n, new in lengths]
        for r in reqs:
            r.wait(300)
        return reqs, list(eng.step_log)
    finally:
        eng.close()


@pytest.fixture(scope="module")
def keye_exact():
    """The program with float32 in bfloat16's place (operands, cache and
    weights): what is left against the reference is the mathematics, so
    EVERY output step is compared and none is left out as a near-tie."""
    import jax
    import jax.numpy as jnp

    hf, cfg, params = tiny_keye()
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    old = lm_model.BF16, lm_moe.BF16
    lm_model.BF16 = lm_moe.BF16 = jnp.float32
    try:
        reqs, log = _serve_keye(params, cfg, KEYE_LENGTHS)
    finally:
        lm_model.BF16, lm_moe.BF16 = old
    return hf, cfg, params, reqs, log


@pytest.mark.parametrize("i", range(len(KEYE_LENGTHS)))
def test_sparse_prefill_and_decode_equal_the_reference_at_every_step(
        keye_exact, i):
    hf, _cfg, params, reqs, _log = keye_exact
    req = reqs[i]
    n, new = KEYE_LENGTHS[i]
    assert len(req.tokens) == new and sorted(req.logits) == list(range(new))
    full = np.concatenate([req.prompt, req.tokens[:-1]]).astype(np.int32)
    out = keye_ref.forward(params, hf, full, [n - 1 + s for s in range(new)],
                           operands="float32")
    for s in range(new):
        assert keye_ref.logit_error(req.logits[s], out["logits"][s]) < 1e-3
        assert keye_ref.rank_gap(req.tokens[s], out["logits"][s]) < 1e-4
    # positions with more keys than the selection have a margin
    over = np.isfinite(out["select_gap"])
    assert list(over) == [n - 1 + s + 1 > 16 for s in range(new)]


@pytest.mark.parametrize("how", [{"off": (m,)} for m in keye_ref.MECHANISMS]
                         + [{"select": "dense"}, {"select": "newest"}],
                         ids=lambda h: "-".join(map(str, h.values())))
def test_leaving_a_sparse_mechanism_out_fails_the_comparison(keye_exact, how):
    hf, _cfg, params, reqs, _log = keye_exact
    req = reqs[4]                       # 33 + 9: chunks, selection, decode
    full = np.concatenate([req.prompt, req.tokens[:-1]]).astype(np.int32)
    out = keye_ref.forward(params, hf, full, [32 + s for s in range(9)],
                           operands="float32", **how)
    worst = max(keye_ref.logit_error(req.logits[s], out["logits"][s])
                for s in range(9))
    assert worst > 0.2, how


def test_the_sparse_reference_knows_its_mechanisms(keye_exact):
    hf, _cfg, params, reqs, _log = keye_exact
    with pytest.raises(ValueError):
        keye_ref.forward(params, hf, reqs[0].prompt, [0], off=("no_such",))
    with pytest.raises(ValueError):
        keye_ref.forward(params, hf, reqs[0].prompt, [0], select="oldest")
    with pytest.raises(ValueError):
        keye_ref.forward(params, hf, reqs[0].prompt, [0], operands="int8")


def test_sparse_in_bfloat16_matches_where_the_choices_stand():
    """The program as it ships (bfloat16 operands and cache): positions
    whose router and selection margins stand match the float32
    reference; the rest are near-ties that can fall either way."""
    hf, cfg, params = tiny_keye()
    reqs, log = _serve_keye(params, cfg, [(12, 5), (33, 9), (30, 30)])
    errs = []
    for req in reqs:
        e, gaps = compare_keye(req, hf, params)
        errs += e
        assert not gaps or max(gaps) < 0.1
    assert len(errs) >= 12
    # a flip UPSTREAM (an earlier position's keys, an earlier layer's
    # experts) still reaches a position whose own margins stand: most
    # stand close, none strays as a left-out mechanism does
    assert np.median(errs) < 0.1 and max(errs) < 1.2
    # the device counted what one layer attended and what a dense one
    # would have: every key up to 16, then 16
    for rec in log:
        pos = list(rec["row_pos"])
        if rec["prefill_tokens"]:
            pos += range(rec["context"], rec["context"]
                         + rec["prefill_tokens"])
        assert rec["sparse_keys"] == [sum(min(p + 1, 16) for p in pos),
                                      sum(p + 1 for p in pos)]
        assert "window_pages" not in rec


def _scores(seed, ties):
    """Seeded index scores of 3 x 5 queries over 64 positions, causal,
    with what lies past a query's position at -inf."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(3, 5, 64)).astype(np.float32)
    if ties:                    # a few distinct values: ties everywhere
        x = np.round(x * 2) / 2
        x[0, 0, :] = 0.0        # and one row of nothing but ties
        x[x == 0.0] = 0.0       # no -0.0, as index_scores promises
    last = np.array([40, 9, 63])
    qpos = np.stack([n - np.arange(5)[::-1] for n in last])
    x[np.arange(64)[None, None, :] > qpos[:, :, None]] = -np.inf
    return x, int(last.max()) + 1


@pytest.mark.parametrize("top", [1, 4, 16, 41, 64])
@pytest.mark.parametrize("ties", [False, True])
def test_the_chosen_set_is_lax_top_ks(top, ties):
    import jax.numpy as jnp
    from jax import lax

    x, n_keys = _scores(top, ties)
    chosen = np.asarray(lm_model.select_keys(jnp.asarray(x), top,
                                             jnp.int32(n_keys), block=8))
    vals, idx = lax.top_k(jnp.asarray(x), top)
    want = np.zeros_like(chosen)
    keep = np.asarray(vals) > -np.inf
    s, q, _ = np.nonzero(keep)
    want[s, q, np.asarray(idx)[keep]] = True
    assert (chosen == want).all()
    counts = chosen.sum(-1)
    assert (counts == np.minimum(top, np.isfinite(x).sum(-1))).all()
    # and the rows' own form picks the same positions
    pos, live = lm_model.top_positions(jnp.asarray(x[:, -1]), top)
    for row in range(3):
        assert set(np.asarray(pos)[row][np.asarray(live)[row]]) \
            == set(np.nonzero(chosen[row, -1])[0])


def test_index_scores_and_both_forms_of_the_attention_agree():
    """Index scores over pages against a hand loop; then the masked form
    (every page read, the choice a mask) and the gathered form (the
    chosen keys alone) of the same attention."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    page, bp, width, s, nq = 4, 2, 16, 3, 5
    pages = 1 + s * width
    pool = {name: jnp.asarray(rng.normal(size=(pages, page) + tail),
                              jnp.bfloat16)
            for name, tail in (("ki", (8,)), ("k", (2, 16)), ("v", (2, 16)))}
    last = np.array([40, 9, -1])            # the third sequence is absent
    table = np.zeros((s, width), np.int32)
    perm = rng.permutation(np.arange(1, pages))
    for i in range(s):
        n = last[i] // page + 1 if last[i] >= 0 else 0
        table[i, :n] = perm[i * width:i * width + n]
    qpos = np.stack([n - np.arange(nq)[::-1] for n in last]).astype(np.int32)
    qi = jnp.asarray(rng.normal(size=(s, nq, 4, 8)), jnp.bfloat16)
    wi = jnp.asarray(rng.normal(size=(s, nq, 4)), jnp.float32)
    scores = np.asarray(lm_model.index_scores(
        qi, wi, jnp.asarray(qpos), jnp.asarray(last), pool["ki"],
        jnp.asarray(table), page=page, block_pages=bp, width=64))
    ki = np.asarray(pool["ki"].astype(jnp.float32))
    want = np.full((s, nq, 64), -np.inf, np.float32)
    for i in range(s):
        for j in range(nq):
            for p in range(min(qpos[i, j], last[i]) + 1):
                dots = np.asarray(qi[i, j].astype(jnp.float32)) \
                    @ ki[table[i, p // page], p % page]
                want[i, j, p] = np.sum(np.maximum(dots, 0)
                                       * np.asarray(wi[i, j]))
    assert (np.isfinite(scores) == np.isfinite(want)).all()
    live = np.isfinite(want)
    assert np.abs(scores[live] - want[live]).max() < 1e-5
    assert not np.signbit(scores[scores == 0.0]).any()

    q = jnp.asarray(rng.normal(size=(s, nq, 2, 4, 16)), jnp.bfloat16)
    chosen = lm_model.select_keys(jnp.asarray(scores), 16, jnp.int32(41),
                                  block=bp * page)
    masked, _ = lm_model.paged_attention(
        q, jnp.asarray(qpos), jnp.asarray(last), pool["k"], pool["v"],
        jnp.asarray(table), jnp.zeros((s,), jnp.int32), window=None,
        page=page, block_pages=bp, chosen=chosen)
    pos, alive = lm_model.top_positions(jnp.asarray(scores[:, -1]), 16)
    gathered = lm_model.gathered_attention(
        q[:, -1], pos, alive, pool["k"], pool["v"], jnp.asarray(table),
        page=page)
    assert np.asarray(alive).sum(-1).tolist() == [16, 10, 0]
    assert np.abs(np.asarray(masked[:, -1]) - np.asarray(gathered)).max() \
        < 0.01 * np.abs(np.asarray(gathered)).max()
    assert not np.asarray(gathered[2]).any()        # absent: zeros


def test_a_model_without_window_layers_has_no_window_pool():
    _hf, cfg, _params = tiny_keye()
    assert cfg.window_layers == 0 and cfg.full_layers == 4
    geo = geometry(cfg, rows=4, chunk=16, page=4, cap=128)
    assert geo.window_pages == 0 and geo.ring(cfg.sliding_window) == 0
    kv = empty_cache(cfg, geo)
    assert sorted(kv) == ["k", "ki", "v"]
    assert {p.shape for p in kv["k"] + kv["v"]} == {(geo.full_pages, 4, 2,
                                                     16)}
    assert {p.shape for p in kv["ki"]} == {(geo.full_pages, 4, 8)}
    for chunk in geo.chunk_buckets():
        names = set(plan_shapes(cfg, geo, chunk))
        assert not any("wtab" in n or "wbase" in n for n in names)
    cache = PagedCache(cfg, geo)
    assert cache.window.capacity == 0 and cache.need(50) == (0, 13)
    seq = cache.admit(50)
    seq.extend(50)
    assert len(seq.full) == 13 and not seq.win and seq.trim(50) == 0
    wtab, _base, ftab = seq.tables()
    assert wtab.size == 0 and (ftab[:13] > 0).all()
    assert cache.in_use() == {"window": 0, "full": 13}
    assert seq.release() == 13 and cache.full.reserved == 0
    # a window pool for a model that has no window layer is refused,
    # and so is a pool of one class that the model does need
    with pytest.raises(ValueError, match="window_pages must be 0"):
        Geometry(rows=4, chunk=16, page=4, context_cap=128,
                 window_pages=9, full_pages=9).check(cfg)
    with pytest.raises(ValueError):
        Geometry(rows=4, chunk=16, page=4, context_cap=128,
                 full_pages=0).check(cfg)
    # the afmoe model keeps both classes and gets no indexer pool
    _hf2, afmoe, _p = tiny()
    assert sorted(empty_cache(afmoe, geometry(afmoe))) == ["k", "v"]
    assert "row_wtab" in plan_shapes(afmoe, geometry(afmoe), 0)


@pytest.mark.parametrize("change,named", [
    ({"model_type": "qwen3_moe"}, "qwen3_moe"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                    "indexer_num_kv_heads": 2, "topk": 16}},
     "indexer_num_kv_heads"),
    ({"sa_config": None}, "sa_config.topk")])
def test_what_is_not_built_is_refused_by_name(change, named):
    from lm_helpers import tiny_keye_hf_config

    with pytest.raises(ValueError, match=named):
        LmConfig.from_hf(tiny_keye_hf_config(**change))


def test_the_family_is_chosen_by_model_type_alone():
    from lm_helpers import tiny_hf_config, tiny_keye_hf_config

    keye = LmConfig.from_hf(tiny_keye_hf_config())
    assert (keye.model_type, keye.score_func, keye.index_topk,
            keye.index_heads, keye.index_head_dim) == ("KeyeVL2", "softmax",
                                                       16, 4, 8)
    assert keye.layer_types == ("full_attention",) * 4
    assert (keye.num_dense_layers, keye.num_shared_experts,
            keye.sliding_window, keye.route_scale) == (0, 0, 0, 1.0)
    afmoe = LmConfig.from_hf(tiny_hf_config())
    assert (afmoe.model_type, afmoe.score_func, afmoe.index_topk) == (
        "afmoe", "sigmoid", 0)
    with pytest.raises(ValueError, match="sigmoid"):
        LmConfig.from_hf(tiny_hf_config(score_func="softmax"))
    # bytes a position: K and V of 2 heads x 16, and the indexer's 8
    assert keye.position_bytes() == (0, 4 * (2 * 2 * 16 + 8) * 2)
    assert afmoe.position_bytes() == (5 * 128, 1 * 128)
