"""``qwen3_next`` on the step engine at tiny widths, on the CPU: the
configuration's keys, the DeltaNet's chunkwise form against its
recurrence, prefill over several chunks and then decoding against the
plain reference (``benchmark/reference/qwen3next_ref.py``), a state slot
handed on to a new request, the two shares of the experts against the
uncut layer, the attention kernels at heads of 256 in Pallas's
interpreter, and the family's tensor names through a safetensors file.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_helpers as H
from vlog_tpu.lm import attention_kernel, load, moe
from vlog_tpu.lm import model as lm_model
from vlog_tpu.lm.cache import PagedCache
from vlog_tpu.lm.model import LmConfig

# bfloat16 products at hidden 64 read up to about 0.2 of the logits'
# spread against the float32 reference (measured over the lengths
# below); a part of the mathematics left out reads 1 and more
LOGIT_TOL = 0.35
ROUTE_EPS = 0.05        # a router margin (logits) under this may flip


# ---- the configuration ---------------------------------------------------

def test_from_hf_reads_the_published_keys():
    published = {k: v for k, v in json.loads(
        (H.ROOT / "benchmark" / "configs" / "qwen3_next_80b_4l.json")
        .read_text()).items() if not isinstance(v, (dict, list, str))
        or k in ("model_type", "hidden_act", "mlp_only_layers")}
    published.update(num_hidden_layers=48, num_experts=512)
    del published["published_num_experts"]
    cfg = LmConfig.from_hf(published)
    assert cfg.model_type == "qwen3_next" and cfg.num_layers == 48
    assert cfg.layer_types[:4] == ("linear_attention",) * 3 + (
        "full_attention",)
    assert (cfg.linear_layers, cfg.full_layers, cfg.window_layers) \
        == (36, 12, 0)
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.rotary_dim) \
        == (2048, 16, 2, 256, 64)
    assert (cfg.linear_key_heads, cfg.linear_value_heads,
            cfg.linear_key_dim, cfg.linear_value_dim, cfg.linear_conv) \
        == (16, 32, 128, 128, 4)
    assert cfg.conv_dim == 8192 and cfg.held is None
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.num_shared_experts,
            cfg.moe_intermediate_size) == (512, 10, 1, 512)
    assert cfg.score_func == "softmax" and cfg.route_norm
    # K and V of the full layers only: 2 heads of 256, 2 B, K and V
    assert cfg.position_bytes() == (0, 12 * 2048)
    four = LmConfig.from_hf({**published, "num_hidden_layers": 4,
                             "num_experts": 256,
                             "published_num_experts": 512})
    assert four.held == (0, 256) and four.router_experts == 512
    assert four.position_bytes() == (0, 2048)
    # a slot: three layers of a 32 x 128 x 128 float32 state and a
    # 3 x 8,192 bfloat16 conv tail
    geo = lm_model.Geometry(rows=2, full_pages=2)
    kv = jax.eval_shape(lambda: lm_model.empty_cache(four, geo))
    slot = sum(x.size * x.dtype.itemsize // geo.rows
               for x in kv["state"] + kv["conv"])
    assert slot == 3 * (2_097_152 + 49_152) == 6_438_912
    assert [x.shape for x in kv["k"]] == [(2, 256, 4, 128)]   # halves


@pytest.mark.parametrize("over,name", [
    ({"decoder_sparse_step": 2}, "decoder_sparse_step != 1"),
    ({"mlp_only_layers": [1]}, "mlp_only_layers"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"layer_types": ["linear_attention", "sliding_attention",
                      "linear_attention", "full_attention"]},
     "layer kinds other than")])
def test_from_hf_refuses_what_is_not_built_by_name(over, name):
    with pytest.raises(ValueError, match="qwen3_next: not built: .*" + name):
        LmConfig.from_hf(H.tiny_qwen_hf_config(**over))


# ---- the DeltaNet's two forms ----------------------------------------------

def _recurrence(q, k, v, g, beta, s):
    """The gated delta rule position by position, in float64."""
    q, k, v, g, beta, s = (np.asarray(x, np.float64)
                           for x in (q, k, v, g, beta, s))
    out = np.zeros(v.shape)
    for t in range(q.shape[0]):
        s = s * np.exp(g[t])[:, None, None]
        delta = (v[t] - np.einsum("hkv,hk->hv", s, k[t])) * beta[t][:, None]
        s = s + k[t][:, :, None] * delta[:, None, :]
        out[t] = np.einsum("hkv,hk->hv", s, q[t])
    return out, s


def _gdn_case(seed, t, heads=3, dk=8, dv=6):
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(t, heads, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = rng.normal(size=(t, heads, dk)) * dk ** -0.5
    v = rng.normal(size=(t, heads, dv))
    g = -rng.uniform(0.0, 1.5, size=(t, heads)) ** 3
    beta = rng.uniform(0.05, 1.0, size=(t, heads))
    s0 = rng.normal(size=(heads, dk, dv)) * 0.3
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, s0)]


@pytest.mark.parametrize("sub", [1, 4, 8, 32])
def test_the_chunkwise_form_equals_the_recurrence(sub):
    q, k, v, g, beta, s0 = _gdn_case(1, 32)
    want, want_s = _recurrence(q, k, v, g, beta, s0)
    got, got_s = lm_model.gdn_chunk(q, k, v, g, beta, s0, sub=sub)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), want_s, rtol=2e-5,
                               atol=2e-5)
    # and the rows' form, one position at a time, from the same state
    s = s0[None]
    for t in range(32):
        o, s = lm_model.gdn_rows(q[t:t + 1], k[t:t + 1], v[t:t + 1],
                                 g[t:t + 1], beta[t:t + 1], s)
        np.testing.assert_allclose(np.asarray(o[0]), want[t], rtol=2e-5,
                                   atol=2e-5)


def test_any_split_into_chunks_carries_the_state_and_padding_is_inert():
    q, k, v, g, beta, s0 = _gdn_case(2, 40)
    want, want_s = _recurrence(q, k, v, g, beta, s0)
    s, outs = s0, []
    for lo, hi in ((0, 7), (7, 23), (23, 40)):      # 7, 16 and 17 real
        n, bucket = hi - lo, 24                     # padded to one bucket
        pad = bucket - n

        def fill(x, value=0.0):
            return jnp.concatenate([x[lo:hi], jnp.full(
                (pad,) + x.shape[1:], value, x.dtype)])

        live = jnp.arange(bucket) < n
        o, s = lm_model.gdn_chunk(
            fill(q, 1.0), fill(k, 0.5), fill(v, 3.0),
            jnp.where(live[:, None], fill(g), 0.0),
            jnp.where(live[:, None], fill(beta), 0.0), s, sub=8)
        outs.append(np.asarray(o[:n]))
    np.testing.assert_allclose(np.concatenate(outs), want, rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, rtol=2e-5, atol=2e-5)


# ---- the whole program against the plain reference -------------------------

@pytest.fixture(scope="module")
def qwen():
    return H.tiny_qwen()


def _serve(params, cfg, lengths, **geo):
    eng = H.engine(cfg, params, **geo)
    rng = np.random.default_rng(5)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n), max_new=m,
                       capture=tuple(range(m))) for n, m in lengths]
    for r in reqs:
        r.wait(120)
    log = list(eng.step_log)
    eng.close()
    return reqs, log


def _errors(req, hf, params, **how):
    steps, out = H.qwen_rows(req, hf, params, **how)
    errs = [H.ref.logit_error(req.logits[s], out["logits"][i])
            for i, s in enumerate(steps) if out["route_gap"][i] >= ROUTE_EPS]
    return errs


@pytest.fixture(scope="module")
def served(qwen):
    """Prompts of 37 (two chunks of 16 and one of 5: not a whole
    sub-chunk of 4), 9 and 50 tokens beside each other, then decoding
    through the state slots."""
    _hf, cfg, params = qwen
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(lm_model, "GDN_SUB", 4)
        return _serve(params, cfg, [(37, 5), (9, 4), (50, 6)], rows=4,
                      chunk=16, page=4, cap=128)


def test_prefill_over_chunks_then_decoding_equals_the_reference(qwen,
                                                                served):
    """Every captured step against the reference's full pass."""
    hf, _cfg, params = qwen
    reqs, log = served
    errs = [e for r in reqs for e in _errors(r, hf, params)]
    assert len(errs) >= 5 and max(errs) < LOGIT_TOL
    chunks = [rec for rec in log if rec["prefill_tokens"]]
    assert {rec["gdn_chunk_form"] for rec in chunks} == {"chunkwise"}
    assert {rec["gdn_rows_form"] for rec in log} == {"recurrent"}
    assert {rec["attn_chunk_form"] for rec in chunks} == {"loop"}
    assert max(rec["state_slots"] for rec in log) == 3
    # every valid pair is counted, and those on the held half of them
    for rec in log:
        held, pairs = rec["held_choices"]
        tokens = rec["prefill_tokens"] + rec["decode_rows"]
        assert pairs == tokens * 3 * 4 and 0 <= held <= pairs
        assert held == sum(row[1] for row in rec["expert_load"])


@pytest.mark.parametrize("how", [dict(decay=False), dict(conv_carry=False),
                                 dict(shared_gate=False)])
def test_leaving_a_part_out_fails_the_comparison(qwen, served, how):
    hf, _cfg, params = qwen
    reqs, _ = served
    errs = _errors(reqs[0], hf, params, prompt=37, chunk=16, **how)
    assert errs and max(errs) > 3 * LOGIT_TOL


def test_a_slot_handed_on_starts_from_a_zero_state(qwen):
    """One row: a request, then another in the same row and slot, serves
    exactly what it serves on an engine that never held the first."""
    hf, cfg, params = qwen
    rng = np.random.default_rng(9)
    first, second = rng.integers(0, 512, 30), rng.integers(0, 512, 21)
    eng = H.engine(cfg, params, rows=1, chunk=16, page=4, cap=128)
    a = eng.submit(first, max_new=6)
    b = eng.submit(second, max_new=6, capture=(0, 5))
    a.wait(60)
    got = b.wait(60)
    assert eng.stats()["state"] == {"slots": 1, "in_use": 0}
    eng.close()
    eng = H.engine(cfg, params, rows=1, chunk=16, page=4, cap=128)
    alone = eng.submit(second, max_new=6, capture=(0, 5))
    assert alone.wait(60) == got
    for s in (0, 5):
        np.testing.assert_array_equal(alone.logits[s], b.logits[s])
    eng.close()


def test_the_cache_hands_one_slot_a_request_and_takes_it_back(qwen):
    _hf, cfg, _params = qwen
    geo = H.geometry(cfg, rows=3)
    cache = PagedCache(cfg, geo)
    seqs = [cache.admit(20, row) for row in range(3)]
    assert [s.slot for s in seqs] == [0, 1, 2]
    assert cache.state() == {"slots": 3, "in_use": 3}
    with pytest.raises(ValueError):
        cache.admit(20, 1)
    seqs[1].release()
    assert cache.state()["in_use"] == 2 and cache.admit(20, 1).slot == 1
    # the DeltaNet layers hold no pages: one K and one V pool, the full
    # layer's
    kv = lm_model.empty_cache(cfg, geo)
    assert len(kv["k"]) == len(kv["v"]) == 1
    assert [x.shape for x in kv["state"]] == [(3, 4, 16, 16)] * 3
    assert [x.shape for x in kv["conv"]] == [(3, 3, 128)] * 3
    assert kv["state"][0].dtype == jnp.float32
    assert kv["conv"][0].dtype == jnp.bfloat16


# ---- the experts held here ---------------------------------------------------

def test_the_two_shares_and_the_shared_expert_once_equal_the_uncut_layer():
    rng = np.random.default_rng(3)
    t, h, i, e, k = 24, 16, 8, 8, 3
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(h, e)), jnp.bfloat16)
    gate, up = (jnp.asarray(rng.normal(size=(e, h, i)) * 0.3, jnp.bfloat16)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(e, i, h)) * 0.3, jnp.bfloat16)
    sw = [jnp.asarray(rng.normal(size=s) * 0.3, jnp.bfloat16)
          for s in ((h, i), (h, i), (i, h))]
    sg = jnp.asarray(rng.normal(size=(h, 1)), jnp.bfloat16)
    valid = jnp.arange(t) < 20
    chosen, weights, _ = moe.route(x, router, None, top_k=k, route_norm=True,
                                   route_scale=1.0, score_func="softmax")
    shared = jax.nn.sigmoid(lm_model.mm(x, sg)) * moe.swiglu(x, *sw)
    whole, counted, _ = moe.experts(x, chosen, weights, gate, up, down,
                                    valid)
    parts, held = [], []
    for lo, hi in ((0, 4), (4, 8)):
        y, c, _ = moe.experts(x, chosen, weights, gate[lo:hi], up[lo:hi],
                              down[lo:hi], valid, held=(lo, hi))
        parts.append(y)
        held.append(c)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared),
                               np.asarray(whole + shared), rtol=1e-5,
                               atol=1e-5)
    assert np.concatenate(held).tolist() == np.asarray(counted).tolist()
    # a share computes only its own pairs
    mine = np.asarray((chosen < 4) & valid[:, None]).sum()
    assert int(np.sum(held[0])) == mine and 0 < mine < 20 * k


# ---- the attention kernels at heads of 256 ----------------------------------

NKV, G, HD, PAGE, BP, POOL, WIDTH = 2, 2, 256, 4, 2, 24, 8


def _pools(rng):
    return [jnp.asarray(rng.normal(size=(POOL, PAGE, NKV, HD)), jnp.bfloat16)
            for _ in range(2)]


def _halves(pools):
    """The same pools as ``model.py::kv_tail`` lays a head of 256: two
    128-lane halves a head, the same bytes in the same order."""
    cfg = LmConfig.from_hf(H.tiny_qwen_hf_config(head_dim=HD))
    assert lm_model.kv_tail(cfg) == (2 * NKV, 128)
    return [p.reshape(POOL, PAGE, 2 * NKV, 128) for p in pools]


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6,
                               atol=2e-6)


@pytest.mark.parametrize("halves", [False, True])
@pytest.mark.parametrize("p0,n", [(8, 16), (13, 11)])
def test_the_chunk_kernel_at_heads_of_256_equals_the_loop(monkeypatch, p0,
                                                          n, halves):
    assert attention_kernel.supported(2048, 2, 256, 256)
    rng = np.random.default_rng(p0)
    pk, pv = _pools(rng)
    q = jnp.asarray(rng.normal(size=(1, 16, NKV, G, HD)) * 0.1, jnp.bfloat16)
    table = np.zeros((1, WIDTH), np.int32)
    live = (p0 + n - 1) // PAGE + 1
    table[0, :live] = rng.permutation(np.arange(1, POOL))[:live]
    args = (q, (p0 + jnp.arange(16, dtype=jnp.int32))[None],
            jnp.asarray([p0 + n - 1], jnp.int32), pk, pv, jnp.asarray(table),
            jnp.zeros((1,), jnp.int32))
    kw = dict(window=None, page=PAGE, block_pages=BP)
    loop, _ = lm_model.paged_attention(*args, **kw)
    if halves:
        args = args[:3] + tuple(_halves(args[3:5])) + args[5:]
        _close(lm_model.paged_attention(*args, **kw)[0], loop)
    monkeypatch.setattr(lm_model, "attention_form", lambda *_, **__: "kernel")
    monkeypatch.setattr(attention_kernel, "chunk_attention", functools.partial(
        attention_kernel.chunk_attention, q_tile=8, interpret=True))
    got, _ = lm_model.paged_attention(*args, **kw)
    _close(got, loop)
    assert np.abs(np.asarray(got)[0, :n]).max() > 0.01


@pytest.mark.parametrize("halves", [False, True])
def test_the_rows_kernel_at_heads_of_256_equals_the_loop(monkeypatch, halves):
    assert attention_kernel.rows_supported(2, 8, 256, 256)
    rng = np.random.default_rng(4)
    pk, pv = _pools(rng)
    lasts = (0, 13, -1, 21, 30)
    q = jnp.asarray(rng.normal(size=(5, 1, NKV, G, HD)) * 0.1, jnp.bfloat16)
    table = np.zeros((5, WIDTH), np.int32)
    for i, last in enumerate(lasts):
        if last >= 0:
            table[i, :last // PAGE + 1] = rng.permutation(
                np.arange(1, POOL))[:last // PAGE + 1]
    last = jnp.asarray(lasts, jnp.int32)
    args = (q, jnp.maximum(last, 0)[:, None], last, pk, pv,
            jnp.asarray(table), jnp.zeros((5,), jnp.int32))
    kw = dict(window=None, page=PAGE, block_pages=BP)
    loop, _ = lm_model.paged_attention(*args, **kw)
    if halves:
        args = args[:3] + tuple(_halves(args[3:5])) + args[5:]
    monkeypatch.setattr(lm_model, "attention_form",
                        lambda *_, **__: "rows_kernel")
    monkeypatch.setattr(attention_kernel, "rows_attention", functools.partial(
        attention_kernel.rows_attention, interpret=True))
    got, _ = lm_model.paged_attention(*args, **kw)
    _close(got, loop)
    assert not np.asarray(got)[2].any() and np.asarray(got)[4].any()


# ---- the family's tensor names ------------------------------------------------

def test_load_round_trip_of_the_familys_tensor_names(tmp_path, qwen):
    hf, cfg, params = qwen
    path = H.save_model_dir(tmp_path / "qwen", hf, params)
    from safetensors.flax import load_file, save_file
    sd = load_file(str(path / "model.safetensors"))
    for li in range(3):         # the conv as published: (channels, 1, taps)
        name = f"model.layers.{li}.linear_attn.conv1d.weight"
        sd[name] = sd[name][:, None, :]
    # a checkpoint holds every expert; this chip reads the first eight
    for li in range(4):
        for e in range(8, 16):
            for proj in ("gate_proj", "up_proj", "down_proj"):
                sd[f"model.layers.{li}.mlp.experts.{e}.{proj}.weight"] = \
                    jnp.zeros((1,), jnp.bfloat16)
    save_file(sd, str(path / "model.safetensors"))
    assets = load.load_model_dir(path)
    assert assets.cfg == cfg
    flat_a, tree_a = jax.tree.flatten(assets.params)
    flat_b, tree_b = jax.tree.flatten(params)
    assert tree_a == tree_b
    for a, b in zip(flat_a, flat_b):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))
    for want in ("linear_attn.in_proj_qkvz.weight",
                 "linear_attn.in_proj_ba.weight", "linear_attn.A_log",
                 "linear_attn.dt_bias", "linear_attn.norm.weight",
                 "linear_attn.out_proj.weight",
                 "mlp.shared_expert_gate.weight",
                 "mlp.shared_expert.up_proj.weight", "mlp.gate.weight"):
        assert f"model.layers.0.{want}" in sd, want
    for want in ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"):
        assert f"model.layers.3.self_attn.{want}.weight" in sd, want
    assert sd["model.layers.0.linear_attn.conv1d.weight"].shape == (128, 1, 4)
    lp = assets.params["layers"][0]
    assert lp["a_log"].dtype == lp["dt_bias"].dtype == jnp.float32
    assert lp["router"].shape == (64, 16) and lp["e_gate"].shape[0] == 8
    del sd["model.layers.1.linear_attn.dt_bias"]
    save_file(sd, str(path / "model.safetensors"))
    with pytest.raises(load.LmLoadError, match="dt_bias"):
        load.load_model_dir(path)
