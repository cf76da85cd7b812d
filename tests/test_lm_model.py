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
