"""The beam program's self-K/V cache is written once and never moved.

``_generate_beam_jit`` keeps beam history as an ancestry table that
masks a per-window self-attention (asr/decode.py, asr/model.py). Three
things are pinned here, on the CPU at tiny widths: the program gives
token for token what a beam search gives that keeps the former
formulation (the cache gathered by parent every step, each row attending
over its own cache row); windows cannot touch each other; and the
program's scan body holds the in-place writes and nothing else that
moves a cache (counts of operations, never a time; what the TPU compiler
makes of it is tests/test_beam_cache_layout.py's). Since PR 30 the
cross-attention K/V stays by window too: no equation of the beam program
holds a ``windows x beam``-row array with a source axis, and a step with
K/V by window gives the logits of a step with K/V repeated per row.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from vlog_tpu.asr import decode
from vlog_tpu.asr.model import (DecoderCache, StepCache, WhisperConfig,
                                _attention, _beam_attention, _split_heads,
                                cross_kv, decoder_step, encode,
                                init_random_params)

CFG = WhisperConfig(
    d_model=32, encoder_layers=1, decoder_layers=2,
    encoder_attention_heads=2, decoder_attention_heads=2,
    encoder_ffn_dim=64, decoder_ffn_dim=64, vocab_size=120,
    max_source_positions=50, max_target_positions=40)
VOCAB = dict(sot=100, eot=99, ts_begin=110, no_speech=105)
PROMPT = (100, 101, 102)
K = 5


@pytest.fixture(scope="module")
def params():
    # N(0, 0.02^2) matrices at d_model 32 leave the tokens deaf to audio
    # and history (every window decodes one repeated token): at 12 times
    # that both windows give varied tokens and reorder their beams at
    # nearly every step
    return {name: (w * 12.0 if w.ndim >= 2 else w)
            for name, w in init_random_params(CFG, seed=12).items()}


def _mel(seed: int, windows: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal((windows, 80, 100)).astype(np.float32)


def _run(params, mel, *, max_new: int, timestamps: bool = True,
         beam: int = K, page: DecoderCache | None = None):
    rows = mel.shape[0] * beam
    cache = page if page is not None else DecoderCache.create(
        CFG, rows, len(PROMPT) + max_new)
    toks, nsp, cache = decode._generate_beam_jit(
        params, jnp.asarray(mel), jnp.asarray(PROMPT, jnp.int32),
        jnp.zeros(CFG.vocab_size), jnp.zeros(CFG.vocab_size), cache,
        cfg=CFG, max_new=max_new, timestamps=timestamps, beam=beam, **VOCAB)
    return np.asarray(toks), np.asarray(nsp), cache


def _gathering_beam_search(params, mel, *, max_new: int, timestamps: bool):
    """The former formulation, kept here as the reference: the whole
    cache is gathered by parent row each step (``jnp.take``) and
    ``decoder_step`` runs with no table, so each row attends over its
    own, reordered, cache row. Same rules, same top-K, same selection.
    Returns (best tokens, no-speech probability, parents of every step)."""
    eot, ts_begin = VOCAB["eot"], VOCAB["ts_begin"]
    b, k = mel.shape[0], K
    bk = b * k
    neg = jnp.finfo(jnp.float32).min
    ckv = [(jnp.repeat(ck, k, axis=0), jnp.repeat(cv, k, axis=0))
           for ck, cv in cross_kv(params, encode(params, mel, CFG), CFG)]
    cache = StepCache.create(CFG, bk, len(PROMPT) + max_new)
    for i, tok in enumerate(PROMPT):
        logits, cache = decoder_step(params, jnp.full((bk,), tok, jnp.int32),
                                     jnp.int32(i), cache, ckv, CFG)
    nsp = jax.nn.softmax(logits.reshape(b, k, -1)[:, 0],
                         axis=-1)[:, VOCAB["no_speech"]]
    scores = jnp.tile(jnp.concatenate(
        [jnp.zeros((1,)), jnp.full((k - 1,), neg)]), (b,))
    seqs = jnp.full((bk, max_new), eot, jnp.int32)
    last = jnp.full((bk,), PROMPT[-1], jnp.int32)
    penult = jnp.full((bk,), PROMPT[-2], jnp.int32)
    last_ts = jnp.full((bk,), ts_begin - 1, jnp.int32)
    finished = jnp.zeros((bk,), bool)
    parents = []
    v = CFG.vocab_size
    for step in range(max_new):
        lg = logits
        if timestamps:
            lg = decode.apply_timestamp_rules(
                lg, last, penult, last_ts, jnp.int32(step),
                ts_begin=ts_begin, eot=eot)
        lp = jax.nn.log_softmax(lg, axis=-1)
        lp = jnp.where(finished[:, None],
                       jnp.where(jnp.arange(v)[None, :] == eot, 0.0, neg), lp)
        top_s, top_i = jax.lax.top_k(
            (scores[:, None] + lp).reshape(b, k * v), k)
        parent = top_i // v
        parents.append(np.asarray(parent))
        token = (top_i % v).astype(jnp.int32).reshape(bk)
        gparent = (parent + jnp.arange(b)[:, None] * k).reshape(bk)
        scores = top_s.reshape(bk)
        seqs = jnp.take(seqs, gparent, axis=0).at[:, step].set(token)
        penult = jnp.take(last, gparent, axis=0)
        last = token
        last_ts = jnp.where(token >= ts_begin, token,
                            jnp.take(last_ts, gparent, axis=0))
        finished = jnp.take(finished, gparent, axis=0) | (token == eot)
        cache = StepCache(k=jnp.take(cache.k, gparent, axis=1),
                          v=jnp.take(cache.v, gparent, axis=1))
        logits, cache = decoder_step(params, token,
                                     jnp.int32(len(PROMPT) + step), cache,
                                     ckv, CFG)
    lens = jnp.sum(seqs != eot, axis=1).astype(jnp.float32)
    norm = scores / jnp.maximum(lens, 1.0)
    norm = jnp.where(finished, norm, norm - 1e9)
    best = jnp.argmax(norm.reshape(b, k), axis=1) + jnp.arange(b) * k
    return (np.asarray(jnp.take(seqs, best, axis=0)), np.asarray(nsp),
            np.stack(parents))


@pytest.mark.parametrize("timestamps", [True, False])
def test_beam_program_matches_a_cache_gathering_beam_search(params,
                                                            timestamps):
    """K = 5, two windows of different audio, 20 steps: the tokens are
    those of a beam search that gathers its cache by parent, and the run
    is long enough that both windows reorder their beams (a parent other
    than the identity), more than once."""
    mel = _mel(21, 2)
    want, want_nsp, parents = _gathering_beam_search(
        params, jnp.asarray(mel), max_new=20, timestamps=timestamps)
    got, got_nsp, _ = _run(params, mel, max_new=20, timestamps=timestamps)
    for w in range(2):
        reordering = [s for s in range(1, 20)
                      if (parents[s, w] != np.arange(K)).any()]
        assert len(reordering) >= 3, (w, parents[:, w])
    assert not (want[0] == want[1]).all()
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got_nsp, want_nsp, rtol=0, atol=1e-7)


def test_a_window_is_bit_equal_alone_and_beside_others(params):
    """At one bucket shape (4 windows x 5 beams, as the engine runs it):
    a window's tokens and no-speech probability are bit-equal whether
    the other rows are zero padding, another window's audio, or that
    audio scaled up; and the neighbour's are its own whichever window
    sits beside it. Nothing crosses a window."""
    a, b = _mel(31, 1)[0], _mel(32, 1)[0]
    zero = np.zeros_like(a)
    alone, alone_nsp, _ = _run(params, np.stack([a, zero, zero, zero]),
                               max_new=16)
    beside, beside_nsp, _ = _run(params, np.stack([a, b, zero, zero]),
                                 max_new=16)
    loud, loud_nsp, _ = _run(params, np.stack([a, 7.0 * b, b, zero]),
                             max_new=16)
    assert not (beside[0] == beside[1]).all()
    for toks, nsp in ((beside, beside_nsp), (loud, loud_nsp)):
        np.testing.assert_array_equal(toks[0], alone[0])
        assert nsp[0].tobytes() == alone_nsp[0].tobytes()
    np.testing.assert_array_equal(loud[2], beside[1])
    # zero-padded rows decode too, and alike
    np.testing.assert_array_equal(alone[1], alone[3])
    np.testing.assert_array_equal(beside[2], alone[1])


def test_a_dirty_page_cannot_reach_the_tokens(params):
    """The pool hands pages back unwashed: a page a previous generation
    filled (other audio, another beam order) and one full of 1e6 give
    bit-equal tokens to a zeroed page. The mask admits only (slot,
    position) pairs written in this generation. (A masked term weighs
    exactly 0, so any finite leftover is safe; NaN never was, before
    the table or since: 0 x NaN.)"""
    mel = _mel(41, 2)
    clean, clean_nsp, _ = _run(params, mel, max_new=12)
    _, _, used = _run(params, _mel(42, 2), max_new=12)
    assert float(jnp.abs(used.k).max()) > 0.0
    huge = DecoderCache(k=jnp.full_like(used.k, 1e6),
                        v=jnp.full_like(used.v, -1e6))
    for page in (used, huge):
        toks, nsp, _ = _run(params, mel, max_new=12, page=page)
        np.testing.assert_array_equal(toks, clean)
        assert nsp.tobytes() == clean_nsp.tobytes()


def _held(eqn) -> list:
    """The jaxprs an equation holds (a scan's body, an inner jit's)."""
    subs = [getattr(sub, "jaxpr", sub) for value in eqn.params.values()
            for sub in (value if isinstance(value, (list, tuple))
                        else [value])]
    return [j for j in subs if hasattr(j, "eqns")]


def _walk(jaxpr, prefix: str = ""):
    """(equation, its whole name stack) of every equation, those of
    held jaxprs under their holder's stack."""
    for eqn in jaxpr.eqns:
        stack = "/".join(p for p in (prefix, str(eqn.source_info.name_stack))
                         if p)
        yield eqn, stack
        for j in _held(eqn):
            yield from _walk(j, stack)


WINDOWS = 3        # not the layer count: shapes tell them apart
MAX_LEN = len(PROMPT) + 6


def _traced(params, beam: int):
    """Every equation of the generate program at 3 windows and 6 steps,
    and those of its scan body: (equation, name stack) of the ones that
    do something themselves, not of those that hold a jaxpr."""
    kw = dict(cfg=CFG, max_new=6, timestamps=True, beam=beam, **VOCAB)
    jaxpr = jax.make_jaxpr(
        lambda *a: decode._generate_beam_jit(*a, **kw))(
        params, jnp.zeros((WINDOWS, 80, 2 * CFG.max_source_positions)),
        jnp.asarray(PROMPT, jnp.int32),
        jnp.zeros(CFG.vocab_size), jnp.zeros(CFG.vocab_size),
        DecoderCache.create(CFG, WINDOWS * beam, MAX_LEN))
    (scan,) = [e for e, _ in _walk(jaxpr.jaxpr) if e.primitive.name == "scan"]
    return tuple([(e, st) for e, st in _walk(j) if not _held(e)]
                 for j in (jaxpr.jaxpr, scan.params["jaxpr"].jaxpr))


@pytest.mark.parametrize("beam", [K, 1])
def test_scan_body_writes_the_cache_in_place_and_never_moves_it(
        params, beam):
    """What the scan body does to the carried ``(layers, rows, max_len,
    d_model)`` arrays: ``2 x decoder_layers`` ``dynamic_update_slice``
    of one position each (the projection's ``(rows, 1, d_model)`` slab),
    one slice a layer for K and for V to read the layer back, the
    layout pin on the two arrays the step hands on, and nothing else:
    no gather, concatenate (``jnp.stack``) or ``dynamic_slice`` of
    anything as large as one layer of the cache. The rank-5 page of the
    program's boundary never enters the body. The program's only gather
    of anything with a ``max_len`` axis is the ancestry table's, under
    its own named scope; a beam of one is the same body over one slot a
    window."""
    _, body = _traced(params, beam)
    windows, max_len = WINDOWS, MAX_LEN
    rows = windows * beam
    carried = (CFG.decoder_layers, rows, max_len, CFG.d_model)
    page = (CFG.decoder_layers, rows, CFG.decoder_attention_heads, max_len,
            CFG.d_model // CFG.decoder_attention_heads)
    on_cache: dict[str, list] = {}
    for eqn, stack in body:
        assert page not in _shapes(eqn), (eqn, stack)
        if any(getattr(v.aval, "shape", ()) == carried for v in eqn.invars):
            on_cache.setdefault(eqn.primitive.name, []).append((eqn, stack))
    assert set(on_cache) == {"dynamic_update_slice", "slice",
                             "layout_constraint"}
    writes = on_cache["dynamic_update_slice"]
    assert len(writes) == 2 * CFG.decoder_layers
    for eqn, stack in writes:
        assert eqn.invars[1].aval.shape == (1, rows, 1, CFG.d_model)
        assert eqn.outvars[0].aval.shape == carried
        assert "asr.decoder_step.cache_update" in stack
    assert len(on_cache["slice"]) == 2 * CFG.decoder_layers
    assert all(e.outvars[0].aval.shape == (1,) + carried[1:]
               and "asr.decoder_step.self_attn" in st
               for e, st in on_cache["slice"])
    assert len(on_cache["layout_constraint"]) == 2      # K and V, once
    # nothing else in the body makes or gathers a layer's worth of cache
    # (an array with a max_len axis and a layer's elements or more)
    layer = int(np.prod(carried[1:]))
    assert max_len not in (CFG.vocab_size, CFG.d_model,
                           CFG.max_target_positions)
    for eqn, stack in body:
        if eqn.primitive.name in ("gather", "concatenate", "dynamic_slice"):
            assert all(max_len not in sh or int(np.prod(sh)) < layer
                       for sh in _shapes(eqn)), (eqn, stack)
    table = [(e, st) for e, st in body if "asr.beam_ancestry" in st]
    # the table: (windows, K, max_len) int32, gathered by parent and
    # written at one position; no other gather sees a max_len axis
    assert {e.primitive.name for e, _ in table} >= {
        "gather", "dynamic_update_slice"}
    with_len = [(e, st) for e, st in body
                if e.primitive.name == "gather"
                and max_len in e.invars[0].aval.shape[1:]]
    assert [(e.invars[0].aval.shape, str(e.invars[0].aval.dtype))
            for e, _ in with_len] == [((windows, beam, max_len), "int32")]
    assert "asr.beam_ancestry" in with_len[0][1]


def _shapes(eqn) -> list[tuple]:
    return [tuple(getattr(v.aval, "shape", ()))
            for v in (*eqn.invars, *eqn.outvars)]


@pytest.mark.parametrize("beam", [K, 1])
def test_cross_kv_stays_by_window_through_the_whole_program(params, beam):
    """The beam program never makes a per-row copy of the cross-K/V: no
    equation, before the scan, inside its body or after it, has an
    operand or output that leads with ``windows x beam`` rows (flat or
    folded) AND has a ``max_source_positions`` axis, or that is as large
    as the five-fold tile. Inside the body the only equations
    that touch the ``(windows, heads, source, hd)`` K/V are the two
    products of ``asr.decoder_step.cross_attn``, ``2 x decoder_layers``
    in all, each over a ``(windows, beam)`` query block or its
    ``(windows, heads, beam, source)`` scores. A beam of one keeps one
    K/V row per query row and the per-row products."""
    windows = WINDOWS
    src = CFG.max_source_positions
    nh = CFG.decoder_attention_heads
    hd = CFG.d_model // nh
    assert src not in (MAX_LEN, CFG.vocab_size, CFG.d_model)
    everything, body = _traced(params, beam)
    ckv = (windows, nh, src, hd)
    on_ckv = [(e, st) for e, st in body if ckv in _shapes(e)]
    assert len(on_ckv) == 2 * CFG.decoder_layers
    assert {e.primitive.name for e, _ in on_ckv} == {"dot_general"}
    assert all("asr.decoder_step.cross_attn" in st for _, st in on_ckv)
    others = sorted({sh for e, _ in on_ckv for sh in _shapes(e)} - {ckv})
    if beam == 1:
        # a row is a window, the products stay per row
        assert others == sorted({(windows, nh, 1, hd), (windows, nh, 1, src)})
        return
    rows = windows * beam
    # a (windows, beam) query block in, (windows, heads, beam, source)
    # scores between the two products, a beam-sized block out
    assert (windows, beam, nh, hd) in others
    assert (windows, nh, beam, src) in others
    assert all(beam in sh and int(np.prod(sh)) <= rows * nh * src
               for sh in others), others
    # no per-row K/V anywhere: nothing with a source axis leads with
    # windows x beam rows, flat or folded, or is as large as a tile
    for eqn, stack in everything:
        for shape in _shapes(eqn):
            if src in shape:
                assert shape[0] != rows, (eqn, stack)
                assert shape[:2] != (windows, beam), (eqn, stack)
                assert int(np.prod(shape)) < rows * nh * src * hd, (eqn,
                                                                    stack)
    assert "asr.cross_kv.tile" not in {
        part for _, st in everything for part in st.split("/")}


@pytest.mark.parametrize("position", [0, 2, 7])
def test_a_step_with_kv_by_window_gives_the_logits_of_kv_per_row(
        params, position):
    """``decoder_step`` over 3 windows x 5 beams, the same tokens, cache
    and ancestry table: cross-K/V by window, (3, H, source, hd), against
    the same K/V repeated per row, (15, H, source, hd). The logits agree
    to 1e-5; the first layer's cache entry, written before any
    cross-attention, is bit-equal, and the later layers' agree as the
    logits do."""
    windows, max_len = 3, 10
    rows = windows * K
    rng = np.random.default_rng(50 + position)
    enc = encode(params, jnp.asarray(_mel(51, windows)), CFG)
    by_window = cross_kv(params, enc, CFG)
    per_row = [(jnp.repeat(ck, K, axis=0), jnp.repeat(cv, K, axis=0))
               for ck, cv in by_window]
    assert by_window[0][0].shape[0] == windows
    assert per_row[0][0].shape[0] == rows
    cache = StepCache(
        k=jnp.asarray(rng.standard_normal(
            (CFG.decoder_layers, rows, max_len, CFG.d_model)), jnp.float32),
        v=jnp.asarray(rng.standard_normal(
            (CFG.decoder_layers, rows, max_len, CFG.d_model)), jnp.float32))
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, rows), jnp.int32)
    anc = jnp.asarray(rng.integers(0, K, (windows, K, max_len)), jnp.int32)
    pos = jnp.int32(position)
    for table in (anc, None):
        got, got_cache = decoder_step(params, tokens, pos, cache, by_window,
                                      CFG, table)
        want, want_cache = decoder_step(params, tokens, pos, cache, per_row,
                                        CFG, table)
        assert got.shape == (rows, CFG.vocab_size)
        assert float(jnp.abs(want).max()) > 1.0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got_cache.k[0]),
                                      np.asarray(want_cache.k[0]))
        np.testing.assert_allclose(np.asarray(got_cache.v),
                                   np.asarray(want_cache.v),
                                   rtol=0, atol=1e-5)
    # windows differ: K/V by window is not one window's broadcast to all
    assert not np.allclose(np.asarray(got[:K]), np.asarray(got[K:2 * K]))


@pytest.mark.parametrize("beam", [K, 1])
@pytest.mark.parametrize("form", ["beam", "per_row"])
def test_attention_over_a_step_cache_layer_is_the_per_row_attention(
        form, beam):
    """One layer of a :class:`StepCache`, ``(rows, max_len, d_model)``
    with the heads side by side, random K/V, position 6 of 10 and a
    tail of 1e6 behind it. ``beam``: ``_beam_attention`` under an
    ancestry table whose parents cross, against float64 attention of
    each row over the history the table names (the parent's cache
    gathered by row). ``per_row``: the prompt steps' ``_attention`` over
    the layer split into heads, against each row over its own row. To
    1e-6, and the tail weighs nothing."""
    windows, max_len, pos, nh = 3, 10, 6, 4
    hd = 8
    rows = windows * beam
    rng = np.random.default_rng(60 + beam)
    k = rng.standard_normal((rows, max_len, nh * hd)).astype(np.float32)
    v = rng.standard_normal((rows, max_len, nh * hd)).astype(np.float32)
    k[:, pos + 1:] = 1e6
    v[:, pos + 1:] = -1e6
    q = rng.standard_normal((rows, nh, 1, hd)).astype(np.float32)
    valid = jnp.arange(max_len) <= pos
    if form == "beam":
        anc = rng.integers(0, beam, (windows, beam, max_len))
        if beam > 1:
            # crossed for certain: beams 0 and 1 swap slots at position 2
            anc[:, 0, 2], anc[:, 1, 2] = 1, 0
        got = _beam_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              jnp.asarray(anc, jnp.int32), valid)
    else:
        anc = np.broadcast_to(np.arange(beam)[None, :, None],
                              (windows, beam, max_len))
        got = _attention(jnp.asarray(q), _split_heads(jnp.asarray(k), nh),
                         _split_heads(jnp.asarray(v), nh), valid)
    assert got.shape == (rows, nh, 1, hd)
    kh = k.astype(np.float64).reshape(windows, beam, max_len, nh, hd)
    vh = v.astype(np.float64).reshape(windows, beam, max_len, nh, hd)
    t = np.arange(pos + 1)
    for w in range(windows):
        for b in range(beam):
            hist_k = kh[w, anc[w, b, t], t]             # (pos+1, nh, hd)
            hist_v = vh[w, anc[w, b, t], t]
            sc = np.einsum("hd,thd->ht", q[w * beam + b, :, 0], hist_k)
            pr = np.exp(sc - sc.max(axis=1, keepdims=True))
            pr /= pr.sum(axis=1, keepdims=True)
            want = np.einsum("ht,thd->hd", pr, hist_v)
            np.testing.assert_allclose(np.asarray(got[w * beam + b, :, 0]),
                                       want, rtol=0, atol=1e-6)


def test_a_page_survives_the_step_form_and_back():
    """``StepCache.from_page`` and ``to_page`` move every entry of the
    (layers, rows, heads, max_len, hd) page to (layers, rows, max_len,
    d_model) and back: head ``h`` of a position is columns ``h * hd`` to
    ``(h + 1) * hd`` of its slab."""
    rng = np.random.default_rng(70)
    page = DecoderCache(
        k=jnp.asarray(rng.standard_normal((2, 3, 4, 5, 8)), jnp.float32),
        v=jnp.asarray(rng.standard_normal((2, 3, 4, 5, 8)), jnp.float32))
    steps = StepCache.from_page(page)
    assert steps.k.shape == (2, 3, 5, 32)
    np.testing.assert_array_equal(np.asarray(steps.k[1, 2, 4, 8:16]),
                                  np.asarray(page.k[1, 2, 1, 4]))
    back = steps.to_page(4)
    np.testing.assert_array_equal(np.asarray(back.k), np.asarray(page.k))
    np.testing.assert_array_equal(np.asarray(back.v), np.asarray(page.v))
