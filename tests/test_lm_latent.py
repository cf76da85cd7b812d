"""Latent attention under hyper-connections (``xing4_0``) at tiny widths
on the CPU (hidden 64, 4 heads of 16 + 8 over a latent of 16 + 8, four
streams, 1 dense + 3 expert layers, page 4): prefill in chunks then
decoding through the paged latent cache against the plain reference's
full forward pass, the two forms of the attention against each other,
and the residual path's mappings against their definitions."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import vlog_tpu.lm.model as lm_model
import vlog_tpu.lm.moe as lm_moe
from lm_helpers import (engine, random_params, save_model_dir, tiny_xing,
                        tiny_xing_hf_config, xing_ref, xing_rows)
from vlog_tpu.lm import load
from vlog_tpu.lm.cache import PagedCache
from vlog_tpu.lm.engine import default_geometry
from vlog_tpu.lm.model import Geometry, LmConfig

# (prompt, output): under a page, across pages, across a chunk (8), a
# whole number of chunks, many decode steps; the requests overlap, so
# steps carry one request's chunk beside the others' decoding rows
LENGTHS = [(3, 2), (5, 6), (23, 9), (41, 12), (64, 5), (17, 30)]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _serve(params, cfg, lengths, **geo):
    eng = engine(cfg, params, **geo)
    try:
        rng = np.random.default_rng(3)
        reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n), max_new=new,
                           capture=tuple(range(new)))
                for n, new in lengths]
        for r in reqs:
            r.wait(300)
        return reqs, list(eng.step_log), eng.stats(), eng.program_scopes()
    finally:
        eng.close()


@pytest.fixture(scope="module")
def exact():
    """The program with float32 in bfloat16's place (operands, cache and
    weights): what is left against the reference is the mathematics, so
    EVERY output step is compared and none is left out as a near-tie."""
    hf, cfg, params = tiny_xing()
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    old = lm_model.BF16, lm_moe.BF16
    lm_model.BF16 = lm_moe.BF16 = jnp.float32
    try:
        reqs, log, stats, scopes = _serve(params, cfg, LENGTHS)
    finally:
        lm_model.BF16, lm_moe.BF16 = old
    return hf, cfg, params, reqs, log, stats, scopes


# ---- the configuration ---------------------------------------------------

def test_from_hf_reads_the_catalog_rows_keys():
    try:
        rows = [json.loads(ln) for ln in open(CATALOG)]
    except OSError:
        pytest.skip(f"no catalog at {CATALOG}")
    row = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    cfg = LmConfig.from_hf(row["config"])
    assert cfg.model_type == "xing4_0" and cfg.num_layers == 40
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim) \
        == (3584, 32, 192)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (768, 512, 128, 64, 128)
    assert cfg.num_key_value_heads == 1 and cfg.latent_width == 576
    assert (cfg.num_dense_layers, cfg.intermediate_size,
            cfg.moe_intermediate_size) == (2, 9216, 1024)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.num_shared_experts) == (64, 4, 1)
    assert cfg.route_norm and cfg.route_scale == 2.0 \
        and cfg.score_func == "sigmoid"
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_clamp) \
        == (4, 20, 1e-6, (-30.0, 30.0))
    assert cfg.rope_yarn == (64.0, 4096.0, 32.0, 1.0, 1.0, 1.0)
    assert cfg.window_layers == 0 and cfg.sliding_window == 0
    # 576 numbers a position a layer against K and V's 1,024
    assert cfg.position_bytes() == (0, 40 * 1152)
    six = LmConfig.from_hf({**row["config"], "num_hidden_layers": 6})
    assert six.position_bytes() == (0, 6912)


@pytest.mark.parametrize("over,name", [
    ({"n_group": 2}, "n_group != 1"), ({"topk_group": 2}, "topk_group != 1"),
    ({"scoring_func": "softmax"}, "scoring_func other than sigmoid"),
    ({"topk_method": "greedy"}, "topk_method other than noaux_tc"),
    ({"moe_layer_freq": 2}, "moe_layer_freq != 1"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"rope_scaling": {"type": "linear", "factor": 2}},
     "rope_scaling.type other than yarn"),
    ({"hc_mult": 1}, "hc_mult < 2")])
def test_from_hf_refuses_what_is_not_built_by_name(over, name):
    with pytest.raises(ValueError, match="xing4_0: not built: .*"
                       + name.replace(".", r"\.")):
        LmConfig.from_hf(tiny_xing_hf_config(**over))


def test_an_unknown_family_is_refused_with_the_three_that_are_built():
    with pytest.raises(ValueError, match="afmoe, KeyeVL2, xing4_0"):
        LmConfig.from_hf({"model_type": "no_such"})


def test_yarn_frequencies_and_scale_against_hand_values():
    inv = np.asarray(lm_model.yarn_inv_freq(64, 10000.0, 64.0, 4096.0, 32.0,
                                            1.0))
    f = 10000.0 ** (-np.arange(32) / 32.0)
    # low = floor(64 ln(4096 / (2 pi 32)) / (2 ln 10000)) = floor(10.47),
    # high = ceil(64 ln(4096 / (2 pi)) / (2 ln 10000)) = ceil(22.51)
    assert np.allclose(inv[:11], f[:11], rtol=1e-6)         # untouched
    assert np.allclose(inv[23:], f[23:] / 64.0, rtol=1e-6)  # interpolated
    ramp = (16 - 10) / 13.0
    assert np.isclose(inv[16], f[16] / 64 * ramp + f[16] * (1 - ramp),
                      rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    assert np.allclose(inv, xing_ref.yarn_inv_freq(
        64, 10000.0, {"factor": 64, "original_max_position_embeddings": 4096,
                      "beta_fast": 32, "beta_slow": 1}), rtol=1e-6)
    cfg = LmConfig.from_hf(tiny_xing_hf_config(
        qk_nope_head_dim=128, qk_rope_head_dim=64))
    want = 192 ** -0.5 * (0.1 * np.log(64.0) + 1.0) ** 2
    assert np.isclose(lm_model.latent_scale(cfg), want, rtol=1e-9)
    assert np.isclose(want, 0.14469, atol=1e-5)
    # mscale = mscale_all_dim: cos and sin are unscaled
    x = jnp.ones((3, 2, 8), jnp.float32)
    tiny = LmConfig.from_hf(tiny_xing_hf_config())
    assert np.allclose(np.linalg.norm(np.asarray(lm_model.latent_rope(
        tiny, x, jnp.arange(3) * 50)), axis=-1), np.sqrt(8.0), rtol=1e-5)


# ---- the program against the reference -----------------------------------

@pytest.mark.parametrize("i", range(len(LENGTHS)))
def test_latent_prefill_and_decode_equal_the_reference_at_every_step(exact, i):
    hf, _cfg, params, reqs, _log, _stats, _scopes = exact
    req = reqs[i]
    new = LENGTHS[i][1]
    assert len(req.tokens) == new and sorted(req.logits) == list(range(new))
    steps, out = xing_rows(req, hf, params)
    for row, s in enumerate(steps):
        assert xing_ref.logit_error(req.logits[s], out["logits"][row]) < 1e-3
        assert xing_ref.rank_gap(req.tokens[s], out["logits"][row]) < 1e-4
    assert out["hc_defect"] < 1e-5


def test_a_step_mixes_one_requests_chunk_with_the_others_rows(exact):
    _hf, cfg, _params, _reqs, log, stats, scopes = exact
    mixed = [r for r in log if r["prefill_tokens"] and r["decode_rows"]]
    assert mixed and any(r["context"] for r in mixed)   # a later chunk
    for r in log:
        assert r["attn_rows_form"] == "latent_absorbed"
        assert r["attn_chunk_form"] == (
            "latent_expanded_loop" if r["chunk"] else None)
        assert r["rows_context"] == sum(p + 1 for p in r["row_pos"])
        assert 0.0 <= r["hc_defect"] < 1e-5
        assert "window_pages" not in r and "sparse_keys" not in r
        assert len(r["expert_load"]) == cfg.num_layers - cfg.num_dense_layers
    assert stats["attn_rows_form"] == "latent_absorbed"
    assert stats["attn"]["latent_expanded_loop_steps"] \
        == sum(1 for r in log if r["chunk"])
    assert 0.0 < stats["hc_defect_max"] < 1e-5
    named = {s for per_program in scopes.values()
             for s in per_program.values()}
    assert {"lm.attn.latent.project", "lm.attn.latent.rows", "lm.hc.map",
            "lm.hc.pre", "lm.hc.post", "lm.cache.write"} <= named
    chunked = {s for name, per in scopes.items() if not name.endswith("_c0")
               for s in per.values()}
    assert {"lm.attn.latent.expand", "lm.attn.latent.chunk"} <= chunked


@pytest.mark.parametrize("how", [{"h_res": "identity"},
                                 {"sinkhorn_iters": 1}, {"rope_key": False}],
                         ids=lambda h: "-".join(map(str, h)))
def test_leaving_a_part_out_fails_the_comparison(exact, how):
    hf, _cfg, params, reqs, _log, _stats, _scopes = exact
    req = reqs[3]                       # 41 + 12: chunks, pages, decode
    steps, out = xing_rows(req, hf, params, **how)
    worst = max(xing_ref.logit_error(req.logits[s], out["logits"][row])
                for row, s in enumerate(steps))
    assert worst > 0.02, how            # fifty times the exact reading
    if "sinkhorn_iters" in how:
        assert out["hc_defect"] > 0.05


def test_the_reference_knows_its_controls(exact):
    hf, _cfg, params, reqs, _log, _stats, _scopes = exact
    with pytest.raises(ValueError):
        xing_ref.forward(params, hf, reqs[0].prompt, [0], h_res="uniform")


def test_latent_in_bfloat16_stays_within_its_limit():
    """The program as it ships (bfloat16 weights, operands and latents)
    against the float32 reference, positions whose router margin stands:
    the stated limit is 0.25 of the logits' spread at hidden 64, where
    one product rounds as coarsely as at 3,584 and averages over fewer
    terms; a part left out reads 0.5 to 4."""
    hf, cfg, params = tiny_xing()
    reqs, _log, _stats, _scopes = _serve(
        params, cfg, [(12, 5), (41, 12), (30, 30)])
    errs = []
    for req in reqs:
        steps, out = xing_rows(req, hf, params)
        for row, s in enumerate(steps):
            if out["route_gap"][row] < 0.01:
                continue
            errs.append(xing_ref.logit_error(req.logits[s],
                                             out["logits"][row]))
            assert xing_ref.rank_gap(req.tokens[s], out["logits"][row]) < 0.3
    assert len(errs) >= 15
    assert max(errs) < 0.25 and np.median(errs) < 0.1


# ---- the two forms of the attention ---------------------------------------

def _latent_case(seed=0, positions=27, queries=6, heads=4, nope=16, rd=8,
                 rank=16, vd=16, page=4):
    rng = np.random.default_rng(seed)
    n_pages = -(-positions // page)
    table = rng.permutation(np.arange(1, n_pages + 3))[:n_pages].astype(
        np.int32)
    pool = np.zeros((n_pages + 4, rank + rd, page), np.float32)
    lat = rng.normal(size=(positions, rank + rd)).astype(np.float32)
    for p in range(positions):
        pool[table[p // page], :, p % page] = lat[p]
    q = rng.normal(size=(queries, heads, nope + rd)).astype(np.float32)
    w = (rng.normal(size=(rank, heads, nope + vd)) * 0.3).astype(np.float32)
    return q, lat, pool, table, w, positions - queries


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 0.05)])
def test_absorbed_and_expanded_forms_agree(monkeypatch, dtype, tol):
    """The same queries (the last six positions of one sequence over
    seven pages) through both forms and through the definition."""
    monkeypatch.setattr(lm_model, "BF16", dtype)
    q, lat, pool, table, w, p0 = _latent_case()
    nq, scale = q.shape[0], 0.3
    how = dict(nope=16, scale=scale, page=4, block_pages=2)
    pool_d, w_d = jnp.asarray(pool, dtype), jnp.asarray(w, dtype)
    qpos = p0 + jnp.arange(nq, dtype=jnp.int32)
    absorbed = lm_model.absorbed_attention(
        jnp.asarray(q), qpos, qpos, pool_d,
        jnp.tile(jnp.asarray(table)[None], (nq, 1)), w_d, **how)
    expanded = lm_model.expanded_attention(
        jnp.asarray(q), jnp.int32(p0), jnp.int32(p0 + nq - 1), pool_d,
        jnp.asarray(table), w_d, **how)
    assert absorbed.shape == expanded.shape == (nq, 4, 16)
    # the definition: expand every latent, one softmax a query and head
    k_nope = np.einsum("pc,chd->phd", lat[:, :16], w[..., :16])
    v = np.einsum("pc,chd->phd", lat[:, :16], w[..., 16:])
    want = np.zeros((nq, 4, 16), np.float32)
    for i in range(nq):
        t = p0 + i + 1
        s = (np.einsum("hd,phd->hp", q[i, :, :16], k_nope[:t])
             + np.einsum("hd,pd->hp", q[i, :, 16:], lat[:t, 16:])) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        want[i] = np.einsum("hp,phd->hd", p / p.sum(-1, keepdims=True), v[:t])
    spread = np.abs(want).max()
    assert np.abs(np.asarray(absorbed) - want).max() < tol * spread
    assert np.abs(np.asarray(expanded) - want).max() < tol * spread
    assert np.abs(np.asarray(absorbed) - np.asarray(expanded)).max() \
        < tol * spread


def test_the_value_is_a_slice_of_the_key_read_once():
    """``paged_attention`` with one K/V head under 4 query heads, a key
    of 24 and a value that is its first 16 numbers, positions in the
    lanes: the pool is the only array it is given."""
    q, lat, pool, table, _w, p0 = _latent_case()
    qa = jnp.asarray(np.random.default_rng(1).normal(size=(1, 1, 1, 4, 24)),
                     jnp.bfloat16)
    last = jnp.asarray([p0 + 5], jnp.int32)
    out, pages = lm_model.paged_attention(
        qa, last[:, None], last, jnp.asarray(pool, jnp.bfloat16), None,
        jnp.asarray(table)[None], jnp.zeros((1,), jnp.int32),
        window=None, page=4, block_pages=2, value_width=16,
        keys_minor=True, expand=lambda blk: (blk, blk[:, :, :16]))
    assert out.shape == (1, 1, 1, 4, 16) and int(pages[0]) == 7
    lat_b = np.asarray(jnp.asarray(lat, jnp.bfloat16), np.float32)
    s = np.einsum("hd,pd->hp", np.asarray(qa[0, 0, 0], np.float32), lat_b)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = (p / p.sum(-1, keepdims=True)) @ lat_b[:, :16]
    assert np.abs(np.asarray(out[0, 0, 0]) - want).max() < 0.03


# ---- the residual path ------------------------------------------------------

def _maps(cfg, seed=0, tokens=50, scale=1.0, **over):
    cfg = LmConfig.from_hf(tiny_xing_hf_config(**over)) if over else cfg
    rng = np.random.default_rng(seed)
    n, h = cfg.hc_mult, cfg.hidden_size
    xs = jnp.asarray(rng.normal(size=(tokens, n, h)), jnp.float32)
    proj = jnp.asarray(rng.normal(size=(n * h, 2 * n + n * n)) * scale
                       / np.sqrt(n * h), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(2 * n + n * n,)) * 0.1, jnp.float32)
    alpha = jnp.asarray([0.7, 1.3, 1.0], jnp.float32)
    pre, post, res, defect = lm_model.hc_maps(cfg, xs, proj, bias, alpha)
    how = (cfg.hc_sinkhorn_iters, cfg.hc_eps, *cfg.hc_clamp, False)
    with jax.default_matmul_precision("highest"):
        u, post_r, res_r, defect_r = xing_ref._hc_maps(xs, proj, bias, alpha,
                                                       how=how)
    return (np.asarray(pre), np.asarray(post), np.asarray(res),
            np.asarray(defect), np.asarray(u), np.asarray(post_r),
            np.asarray(res_r), float(defect_r), np.asarray(xs))


def test_h_res_is_doubly_stochastic_and_the_mappings_are_the_references():
    _hf, cfg, _params = tiny_xing()
    pre, post, res, defect, u, post_r, res_r, defect_r, xs = _maps(cfg)
    assert res.shape == (4, 4, 50) and pre.shape == post.shape == (4, 50)
    assert np.abs(res.sum(1) - 1).max() < 1e-5      # rows
    assert np.abs(res.sum(0) - 1).max() < 1e-5      # columns
    assert defect.max() < 1e-5 and defect_r < 1e-5
    assert np.all(res > 0) and np.all((pre > 0) & (pre < 1)) \
        and np.all((post > 0) & (post < 2))
    assert np.allclose(res.transpose(2, 0, 1), res_r, atol=1e-6)
    assert np.allclose(post.T, post_r, atol=1e-6)
    assert np.allclose(np.einsum("nt,tnh->th", pre, xs), u, atol=1e-5)
    # not the identity and not uniform: Sinkhorn had work to do
    assert np.abs(res - 0.25).max() > 0.1


def test_sinkhorn_runs_the_iterations_it_is_given():
    _hf, cfg, _params = tiny_xing()
    got = {}
    for iters in (1, 3, 20):
        _pre, _post, res, defect, _u, _p, res_r, defect_r, _xs = _maps(
            cfg, scale=4.0, hc_sinkhorn_iters=iters)
        assert np.allclose(res.transpose(2, 0, 1), res_r, atol=1e-6)
        assert np.isclose(defect.max(), defect_r, atol=1e-6)
        got[iters] = (res, defect.max())
    assert np.abs(got[1][0] - got[3][0]).max() > 1e-3
    assert np.abs(got[3][0] - got[20][0]).max() > 1e-4
    assert got[1][1] > got[3][1] > got[20][1]
    # columns are normalised last: 1 after any count, up to hc_eps over
    # a small column's sum; rows converge
    assert np.abs(got[1][0].sum(0) - 1).max() < 2e-3
    assert np.abs(got[1][0].sum(1) - 1).max() > 1e-2


def test_the_clamp_bounds_the_logits_of_h_res():
    _hf, cfg, _params = tiny_xing()
    loose = _maps(cfg, scale=30.0)[2]
    tight = _maps(cfg, scale=30.0, mhc_h_res_clamp_min=-1,
                  mhc_h_res_clamp_max=1)[2]
    assert np.all(np.isfinite(loose)) and np.all(np.isfinite(tight))
    # logits within [-1, 1]: no entry of exp() under e^-2 of another, and
    # a doubly stochastic 4 x 4 of such entries stays inside (0, 1)
    assert tight.min() > 0.01 and tight.max() < 0.8
    assert loose.max() > 0.99           # unclamped, rows go one-hot
    assert np.abs(loose - tight).max() > 0.2


def _plain_forward(params, hf, ids, positions):
    """``h = h + F(N(h))`` on ONE stream with the reference's stages."""
    r = xing_ref
    eps, rank, nope = hf["rms_norm_eps"], hf["kv_lora_rank"], \
        hf["qk_nope_head_dim"]
    nh = hf["num_attention_heads"]
    inv = jnp.asarray(r.yarn_inv_freq(hf["qk_rope_head_dim"],
                                      hf["rope_theta"], hf["rope_scaling"]))
    ids = np.concatenate([ids, np.zeros(-ids.size % r.ROWS, np.int32)])
    with jax.default_matmul_precision("highest"):
        h = params["embed"][ids].astype(jnp.float32)
        assert h.shape[0] == r.ROWS
        for li, lp in enumerate(params["layers"]):
            cq, c, k_rope = r._latents(
                h, jnp.int32(0), lp["n1"], lp["wqa"], lp["qan"], lp["wkva"],
                lp["kvn"], inv, dims=(rank, eps, True))
            q, k, v = r._expand(
                cq, c, k_rope, jnp.int32(0),
                lp["wqb"].reshape(lp["wqb"].shape[0], nh, -1),
                lp["wkvb"].reshape(rank, nh, -1), inv, dims=(nope,))
            o, = r._attention([q], [k], [v], r.softmax_scale(hf))
            h = h + r._out(lp["wo"], o)
            x = r._norm(h, lp["n2"], eps=eps)
            if li < hf["first_k_dense_replace"]:
                h = h + r._swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
            else:
                y, _gaps = r._experts([x], lp, hf)
                h = h + y[0]
        # one stream where the state holds four equal ones: the final
        # norm takes the scale out
        return np.asarray(r._head(h[jnp.asarray(positions)][:, None, :],
                                  params["final_norm"], params["head"],
                                  eps=eps))


def test_identity_mixing_and_one_hot_reading_is_the_plain_residual_block():
    """``H_res`` forced to the identity (its logits at the clamp's ends),
    ``H_pre`` to the first stream alone, ``H_post`` to 1, the projection
    gated off: every stream is then ``h + F(N(h))`` of the same ``h``,
    and the program serves what a model with ONE plain residual stream
    serves."""
    hf, cfg, params = tiny_xing()
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    n = cfg.hc_mult
    bias = np.zeros(2 * n + n * n, np.float32)
    bias[:n] = [40.0] + [-40.0] * (n - 1)           # H_pre one-hot
    bias[2 * n:] = np.where(np.eye(n), 30.0, -30.0).reshape(-1)
    for lp in params["layers"]:
        for s in "am":
            lp[f"hc{s}_b"] = jnp.asarray(bias)
            lp[f"hc{s}_a"] = jnp.zeros((3,), jnp.float32)
    old = lm_model.BF16, lm_moe.BF16
    lm_model.BF16 = lm_moe.BF16 = jnp.float32
    try:
        (req,), log, _stats, _scopes = _serve(params, cfg, [(21, 4)])
    finally:
        lm_model.BF16, lm_moe.BF16 = old
    full = np.concatenate([req.prompt, req.tokens[:-1]]).astype(np.int32)
    want = _plain_forward(params, hf, full, [20 + s for s in range(4)])
    for s in range(4):
        assert xing_ref.logit_error(req.logits[s], want[s]) < 1e-3
    assert max(r["hc_defect"] for r in log) < 1e-6
    # and the reference agrees with itself on the same forced weights
    _steps, out = xing_rows(req, hf, params)
    assert max(xing_ref.logit_error(out["logits"][s], want[s])
               for s in range(4)) < 1e-3


# ---- the cache and the files ------------------------------------------------

def test_latent_position_bytes_and_pool_accounting():
    _hf, cfg, _params = tiny_xing()
    assert cfg.latent_width == 24
    assert cfg.position_bytes() == (0, 4 * 24 * 2)
    geo = Geometry(rows=4, chunk=8, page=4, context_cap=64, window_pages=0,
                   full_pages=21, kv_block_pages=2)
    kv = lm_model.empty_cache(cfg, geo)
    assert list(kv) == ["lat"] and len(kv["lat"]) == 4
    assert all(p.shape == (21, 24, 4) and p.dtype == jnp.bfloat16
               for p in kv["lat"])
    shapes = lm_model.plan_shapes(cfg, geo, 8)
    assert "row_wtab" not in shapes and "chunk_wtab" not in shapes
    assert shapes["row_ftab"][0] == (4, 16)
    cache = PagedCache(cfg, geo)
    assert cache.pools()["window"]["capacity"] == 0
    assert cache.pools()["full"]["capacity"] == 20
    a = cache.admit(40)                 # ten pages
    b = cache.admit(40)
    assert a is not None and b is not None and cache.admit(4) is None
    a.extend(9)
    assert cache.in_use() == {"window": 0, "full": 3}
    wtab, wbase, ftab = a.tables()
    assert wtab.size == 0 and wbase == 0 and np.count_nonzero(ftab) == 3
    a.release()
    assert cache.admit(4) is not None
    with pytest.raises(ValueError):     # no window layer: no window pool
        geo_w = Geometry(rows=4, chunk=8, page=4, context_cap=64,
                         window_pages=5, full_pages=21)
        geo_w.check(cfg)
    # the default pool: what 4 GiB hold of 6,912 B a position
    six = LmConfig.from_hf(tiny_xing_hf_config(
        hidden_size=3584, kv_lora_rank=512, qk_rope_head_dim=64,
        num_hidden_layers=6))
    geo = default_geometry(six)
    assert geo.window_pages == 0
    assert geo.full_pages == (4 << 30) // (256 * 6912) + 1 == 2428


def test_load_round_trip_of_the_familys_tensor_names(tmp_path):
    hf = tiny_xing_hf_config()
    cfg = LmConfig.from_hf(hf)
    params = random_params(cfg, 5)
    for lp in params["layers"]:         # not the defaults: they must travel
        lp["hca_a"] = lp["hca_a"] * 0.5
        lp["hcm_b"] = lp["hcm_b"] + 0.25
    path = save_model_dir(tmp_path / "xing", hf, params)
    assets = load.load_model_dir(path)
    assert assets.cfg == cfg
    flat_a, tree_a = jax.tree.flatten(assets.params)
    flat_b, tree_b = jax.tree.flatten(params)
    assert tree_a == tree_b
    for a, b in zip(flat_a, flat_b):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))
    from safetensors.flax import load_file
    names = set(load_file(str(path / "model.safetensors")))
    for want in ("self_attn.q_a_proj", "self_attn.q_a_layernorm",
                 "self_attn.q_b_proj", "self_attn.kv_a_proj_with_mqa",
                 "self_attn.kv_a_layernorm", "self_attn.kv_b_proj",
                 "self_attn.o_proj", "self_attn_hc.proj", "mlp_hc.proj"):
        assert f"model.layers.0.{want}.weight" in names, want
    assert "model.layers.0.self_attn_hc.alpha" in names
    assert "model.layers.0.mlp.gate_proj.weight" in names      # dense
    for want in ("mlp.gate.weight", "mlp.gate.e_score_correction_bias",
                 "mlp.experts.7.down_proj.weight",
                 "mlp.shared_experts.up_proj.weight"):
        assert f"model.layers.1.{want}" in names, want
    lp = assets.params["layers"][1]
    assert lp["bias"].dtype == lp["hca_a"].dtype == lp["hcm_b"].dtype \
        == jnp.float32
    assert lp["hca_w"].dtype == jnp.bfloat16 \
        and lp["hca_w"].shape == (4 * 64, 24)
    # a checkpoint that lacks a tensor is refused by its name
    from safetensors.flax import save_file
    sd = load_file(str(path / "model.safetensors"))
    del sd["model.layers.2.mlp_hc.bias"]
    save_file(sd, str(path / "model.safetensors"))
    with pytest.raises(load.LmLoadError, match="mlp_hc.bias"):
        load.load_model_dir(path)
