"""Batched beam-search decoding with Whisper's timestamp grammar.

Faithful port of the generation *rules* the reference relies on through
faster-whisper (beam/VAD pipeline, worker/transcription.py:92-133):
suppress lists, the timestamp pairing grammar, monotonic timestamps, the
timestamp-vs-text probability rule, and no-speech scoring at the first
step. The loop itself is TPU-shaped: one ``lax.scan`` over steps with a
static-shape KV cache, batched over 30 s windows so a long video decodes
as a few large dispatches instead of thousands of small ones.

There is ONE generate program, ``_generate_beam_jit``: beam search at the
production default (``config.WHISPER_BEAM`` is 5, the reference's beam
size), and at ``beam=1`` a beam of one, which picks the arg-max token of
every step. It never moves its self-attention K/V cache: every position
is written once, in place, and beam history is a small ancestry table
that masks a per-window self-attention. Between its edges it carries the
cache as a ``StepCache`` whose physical layout it states (``_pinned``),
so that a one-position write is one position's bytes.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from vlog_tpu.asr.load import SpecialTokens, WhisperAssets
from vlog_tpu.asr.model import (
    DecoderCache,
    StepCache,
    WhisperConfig,
    cross_kv,
    decoder_step,
    encode,
)
from vlog_tpu.obs import hostwait, trace

TIME_PRECISION = 0.02       # seconds per timestamp token step
MAX_INITIAL_TIMESTAMP_INDEX = 50   # first cue within 1.0 s
POLL_S = 1e-3       # the token pull's poll: ~10^3 polls in a 1.3 s tick


# --------------------------------------------------------------------------
# Paged KV-cache pool
# --------------------------------------------------------------------------

class KVCachePool:
    """Static-shape DecoderCache pages, reused across engine ticks.

    The generate program takes the cache as an ARGUMENT and returns the
    final buffers, so the allocation lives here instead of inside the
    jit — the continuous-batching engine used to materialize a fresh
    (layers, B, H, max_len, hd) zeros pair every tick. Pages are keyed
    by exact buffer shape (the engine's batch buckets make these
    recur); a leased page may hold stale K/V from a previous job, which
    is BYTE-SAFE because ``decoder_step`` masks attention to positions
    <= pos and every such position is freshly written during this
    generation's prefill/scan — dirty tail rows are unreachable. The
    scan's ancestry mask is narrower still: it admits only
    (slot, position) pairs its table names, every slot writes its own
    entry at every position <= pos in this generation, and the table
    starts fresh in each call, so nothing a previous tenant left (its
    entries or its beam order) can be reached.
    """

    _MAX_PAGES = 8          # retained pages across all shapes

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._pages: dict[tuple, list[DecoderCache]] = {}
        self.allocs = 0     # fresh page materializations
        self.reuses = 0     # leases served from the pool

    def _shape(self, cfg: WhisperConfig, rows: int, max_len: int) -> tuple:
        hd = cfg.d_model // cfg.decoder_attention_heads
        return (cfg.decoder_layers, rows, cfg.decoder_attention_heads,
                max_len, hd)

    def lease(self, cfg: WhisperConfig, rows: int, max_len: int
              ) -> DecoderCache:
        shape = self._shape(cfg, rows, max_len)
        with self._lock:
            free = self._pages.get(shape)
            if free:
                self.reuses += 1
                return free.pop()
            self.allocs += 1
        return DecoderCache.create(cfg, rows, max_len)

    def release(self, cache: DecoderCache) -> None:
        shape = tuple(cache.k.shape)
        with self._lock:
            if sum(len(v) for v in self._pages.values()) < self._MAX_PAGES:
                self._pages.setdefault(shape, []).append(cache)

    def stats(self) -> dict:
        with self._lock:
            return {"allocs": self.allocs, "reuses": self.reuses,
                    "retained": sum(len(v) for v in self._pages.values())}

    def reset(self) -> None:
        with self._lock:
            self._pages.clear()
            self.allocs = 0
            self.reuses = 0


kv_pool = KVCachePool()


@dataclass
class Segment:
    start_s: float
    end_s: float
    token_ids: list[int]


# --------------------------------------------------------------------------
# Logit rules (vectorized over the batch, jit-safe)
# --------------------------------------------------------------------------

def _suppress_vector(vocab: int, ids: tuple[int, ...]) -> np.ndarray:
    m = np.zeros(vocab, np.float32)
    valid = [i for i in ids if 0 <= i < vocab]
    m[valid] = -np.inf if valid else 0.0
    return m


def apply_timestamp_rules(logits, last, penult, last_ts, step_idx, *,
                          ts_begin: int, eot: int):
    """HF WhisperTimeStampLogitsProcessor semantics, batched.

    ``last``/``penult`` are the two previous generated tokens (prompt
    tokens count as non-timestamps); ``last_ts`` is the most recent
    timestamp token emitted (< ts_begin means none yet).
    """
    neg = jnp.finfo(logits.dtype).min
    v = logits.shape[-1]
    ids = jnp.arange(v)
    is_ts = ids >= ts_begin

    lw_ts = last >= ts_begin
    pen_ts = penult >= ts_begin
    # pair grammar: ts,ts -> no more timestamps; x,ts -> must pair up
    # (timestamp or EOT only)
    mask_ts = lw_ts & pen_ts
    mask_text = lw_ts & ~pen_ts
    logits = jnp.where(mask_ts[:, None] & is_ts[None, :], neg, logits)
    logits = jnp.where(
        mask_text[:, None] & (~is_ts & (ids != eot))[None, :], neg, logits)
    # monotonic timestamps: an unpaired trailing timestamp may repeat
    # (closing a cue at its own start); otherwise strictly increase
    have_ts = last_ts >= ts_begin
    cutoff = jnp.where(have_ts,
                       jnp.where(lw_ts & ~pen_ts, last_ts, last_ts + 1),
                       ts_begin)
    logits = jnp.where(
        is_ts[None, :] & (ids[None, :] < cutoff[:, None]), neg, logits)
    # first generated token must be a timestamp, bounded by max-initial
    first = step_idx == 0
    init_bad = (~is_ts) | (ids > ts_begin + MAX_INITIAL_TIMESTAMP_INDEX)
    logits = jnp.where(first & init_bad[None, :] & (ids != eot)[None, :],
                       neg, logits)
    # probability rule: if mass on timestamps beats the best text token,
    # force a timestamp
    lp = jax.nn.log_softmax(logits, axis=-1)
    ts_lp = jax.nn.logsumexp(jnp.where(is_ts[None, :], lp, neg), axis=-1)
    txt_max = jnp.max(jnp.where(is_ts[None, :], neg, lp), axis=-1)
    force_ts = ts_lp > txt_max
    logits = jnp.where(force_ts[:, None] & ~is_ts[None, :], neg, logits)
    return logits


# --------------------------------------------------------------------------
# Generation: beam search (the reference's quality bar: faster-whisper
# beam_size=5, worker/transcription.py:92-133)
# --------------------------------------------------------------------------

_ROW_MAJOR = Layout(major_to_minor=(0, 1, 2, 3))


def _pinned(cache: StepCache) -> StepCache:
    """The generate program's carry with its physical layout stated:
    row-major, ``d_model`` in the lanes and ``max_len`` in the
    sublanes, so a step's entry for a layer is one sublane row of each
    tile, written in place. Left to itself the TPU compiler puts
    ``max_len`` in the lanes (the attention products want it there) and
    a one-position write then touches every tile of the layer."""
    return jax.tree.map(lambda x: with_layout_constraint(x, _ROW_MAJOR),
                        cache)


@partial(jax.jit, static_argnames=("cfg", "sot", "eot", "ts_begin",
                                   "no_speech", "max_new", "timestamps",
                                   "beam"))
def _generate_beam_jit(params, mel, prompt, suppress_vec, begin_suppress_vec,
                       cache, *, cfg: WhisperConfig, sot: int, eot: int,
                       ts_begin: int, no_speech: int, max_new: int,
                       timestamps: bool, beam: int):
    """Batched beam search over B windows x K beams (flattened to B*K
    cache rows). One ``lax.scan`` over steps; each step scores all K*V
    continuations per window and takes the global top-K. The self-K/V
    cache is never reordered: row ``w*K + s`` is SLOT ``s`` of window
    ``w``, a slot writes its one new position in place each step and the
    entry stays where it was written. Which entries a beam may see is
    the ancestry table ``anc`` int32 (B, K, max_len): ``anc[w, q, t]`` is
    the slot that holds position ``t`` of beam ``q``'s history. After the
    top-K it is gathered by parent (B*K*max_len int32, not the cache)
    and ``anc[:, q, pos] = q``; ``decoder_step`` attends per window over
    the K slots under that table's mask. The K rows of one window thus
    share their cache slots; windows share nothing. The cross-attention
    K/V ``ckv`` stays as ``cross_kv`` gives it, (B, H, source, hd) per
    layer for K and for V: the K beams of a window attend to the same
    audio, so ``decoder_step`` contracts a window's K queries against
    its one K/V row and nothing is tiled per beam. Finished beams
    persist with frozen scores (only EOT continues, at zero cost).
    Selection normalizes by generated length (CTranslate2's
    length_penalty=1).

    ``cache`` comes in and goes out as the pool's page, (layers, B*K,
    H, max_len, hd); nothing a previous tenant left in it can reach the
    tokens (every reachable position is written in this call). In
    between the program carries it as a ``StepCache``, relaid once at
    each edge and pinned (:func:`_pinned`) before the prompt steps and
    at the end of every scan step."""
    enc = encode(params, mel, cfg)
    ckv = cross_kv(params, enc, cfg)
    b = mel.shape[0]
    k = beam
    bk = b * k
    neg = jnp.finfo(jnp.float32).min

    plen = prompt.shape[0]

    cache = _pinned(StepCache.from_page(cache))
    logits = None
    with jax.named_scope("asr.prompt"):
        for i in range(plen):
            tok = jnp.broadcast_to(prompt[i], (bk,))
            logits, cache = decoder_step(params, tok, jnp.int32(i), cache,
                                         ckv, cfg)
        probs0 = jax.nn.softmax(logits.reshape(b, k, -1)[:, 0], axis=-1)
        no_speech_prob = (probs0[:, no_speech] if no_speech >= 0
                          else jnp.zeros(b))

    # beam 0 live at score 0; the rest start at -inf so step 0 fans out
    scores0 = jnp.tile(jnp.concatenate(
        [jnp.zeros((1,), jnp.float32),
         jnp.full((k - 1,), neg, jnp.float32)]), (b,))          # (bk,)

    # ancestry: after the prompt every slot holds its own (identical)
    # entries, so a beam's history starts as its own slot throughout
    own_slot = jnp.broadcast_to(
        jnp.arange(k, dtype=jnp.int32)[None, :, None], (b, k, 1))
    anc0 = jnp.broadcast_to(own_slot, (b, k, plen + max_new))

    def step(carry, step_idx):
        (cache, anc, logits, scores, seqs, last, penult, last_ts,
         finished) = carry
        with jax.named_scope("asr.token_rules"):
            lg = logits + suppress_vec
            lg = jnp.where(step_idx == 0, lg + begin_suppress_vec, lg)
            if timestamps:
                lg = apply_timestamp_rules(lg, last, penult, last_ts,
                                           step_idx, ts_begin=ts_begin,
                                           eot=eot)
            lp = jax.nn.log_softmax(lg, axis=-1)                # (bk, V)
        with jax.named_scope("asr.beam_select"):
            v = lp.shape[-1]
            ids = jnp.arange(v)
            # finished beams: only EOT continues, score unchanged
            lp = jnp.where(finished[:, None],
                           jnp.where(ids[None, :] == eot, 0.0, neg), lp)
            total = scores[:, None] + lp                        # (bk, V)
            top_s, top_i = jax.lax.top_k(total.reshape(b, k * v), k)
            parent = top_i // v                                 # (b, k)
            token = (top_i % v).astype(jnp.int32)
            gparent = (parent + jnp.arange(b)[:, None] * k).reshape(bk)
            token = token.reshape(bk)
            scores = top_s.reshape(bk)

        def take(x):
            return jnp.take(x, gparent, axis=0)

        with jax.named_scope("asr.beam_reorder"):
            seqs = take(seqs).at[:, step_idx].set(token)
            penult = take(last)
            last = token
            last_ts = jnp.where(token >= ts_begin, token, take(last_ts))
            finished = take(finished) | (token == eot)
        pos = (plen + step_idx).astype(jnp.int32)
        with jax.named_scope("asr.beam_ancestry"):
            # a beam inherits its parent's history, then owns position
            # ``pos``, which it is about to write into its own slot
            anc = jnp.take_along_axis(anc, parent[:, :, None], axis=1)
            anc = jax.lax.dynamic_update_slice(
                anc, own_slot, (0, 0, pos))
        nxt_logits, cache = decoder_step(params, token, pos, cache, ckv,
                                         cfg, anc)
        cache = _pinned(cache)
        return ((cache, anc, nxt_logits, scores, seqs, last, penult,
                 last_ts, finished), finished)

    seqs0 = jnp.full((bk, max_new), eot, jnp.int32)
    init = (cache, anc0, logits, scores0, seqs0,
            jnp.full((bk,), prompt[-1], jnp.int32),
            jnp.full((bk,), prompt[-2] if plen >= 2 else sot, jnp.int32),
            jnp.full((bk,), ts_begin - 1, jnp.int32),
            jnp.zeros((bk,), bool))
    (cache, _anc, logits, scores, seqs, *_rest), fin_hist = jax.lax.scan(
        step, init, jnp.arange(max_new))
    finished = _rest[-1]

    # length-normalized selection per window (generated tokens before EOT)
    with jax.named_scope("asr.beam_final"):
        lens = jnp.sum(seqs != eot, axis=1).astype(jnp.float32)
        norm = scores / jnp.maximum(lens, 1.0)
        # prefer finished beams: unfinished get a -1e9 handicap
        norm = jnp.where(finished, norm, norm - 1e9)
        best = jnp.argmax(norm.reshape(b, k), axis=1)           # (b,)
        best_rows = best + jnp.arange(b) * k
        best_seqs = jnp.take(seqs, best_rows, axis=0)
    return (best_seqs, no_speech_prob,
            cache.to_page(cfg.decoder_attention_heads))


def generate_batch(assets: WhisperAssets, mel: jnp.ndarray, *,
                   language: str = "en", task: str = "transcribe",
                   max_new: int | None = None, timestamps: bool = True,
                   beam: int = 1) -> tuple[np.ndarray, np.ndarray]:
    """Decode a batch of 30 s mel windows -> (tokens, no_speech_prob).

    One program serves every ``beam``: batched beam search with
    length-normalized selection (config.WHISPER_BEAM wires the
    production default; the reference runs beam-5), which at ``beam=1``
    keeps each step's arg-max token.

    Window independence is a load-bearing contract: no op here crosses
    windows (per-row conv/argmax, one shared prompt, attention and the
    beam top-K inside one window), so window i's tokens never depend on
    windows j != i — zero-padded rows and co-batched jobs cannot perturb
    a window's output. The K beam rows of ONE window share that window's
    K self-K/V cache slots (each row reads its history through the
    ancestry table's mask; at ``beam=1`` a window is one row and one
    slot), and nothing is shared between windows. The continuous-
    batching engine (asr/engine.py) builds its byte-identical
    solo-vs-packed guarantee on this; tests/test_asr_engine.py breaks
    if it regresses. One shared prompt per call also means callers may
    only co-batch windows agreeing on (language, task, max_new, beam)
    — the engine's BatchKey.

    Two spans split the call for whoever listens (the engine's tick
    record): ``asr.generate.dispatch`` ends when the jitted call has
    returned, ``asr.generate.device_wait`` is the two pulls, that is the
    host's wait for the device program, polled (``obs/hostwait.py::pull``)
    and not blocking; its wait record rides on the span as the attribute
    ``wait``, where the engine's tick record takes it from."""
    with trace.span("asr.generate.dispatch"):
        toks, nsp = _dispatch(assets, mel, language=language, task=task,
                              max_new=max_new, timestamps=timestamps,
                              beam=beam)
    with trace.span("asr.generate.device_wait") as waiting:
        (toks, nsp), waiting.attrs["wait"] = hostwait.pull(
            (toks, nsp), poll_s=POLL_S)
        return toks, nsp


def _dispatch(assets: WhisperAssets, mel: jnp.ndarray, *, language: str,
              task: str, max_new: int | None, timestamps: bool, beam: int):
    """Everything of :func:`generate_batch` up to the jitted call's
    return: prompt and suppress vectors, the lease of the cache page,
    host-to-device copies, the dispatch (and, the first time a shape
    runs, its tracing, lowering and compile). Returns device arrays."""
    st = assets.tokens
    cfg = assets.cfg
    if max_new is None:
        max_new = cfg.max_target_positions // 2
    prompt = [st.sot]
    if st.language_ids:
        prompt.append(st.language_token(language))
        prompt.append(st.transcribe if task == "transcribe" else st.translate)
    if not timestamps:
        prompt.append(st.no_timestamps)
    max_new = min(max_new, cfg.max_target_positions - len(prompt) - 1)
    vocab = cfg.vocab_size
    sup = _suppress_vector(vocab, st.suppress + (st.no_timestamps,))
    bsup = _suppress_vector(vocab, st.begin_suppress)
    kwargs = dict(
        cfg=cfg, sot=st.sot, eot=st.eot, ts_begin=st.timestamp_begin,
        no_speech=st.no_speech if st.no_speech is not None else -1,
        max_new=int(max_new), timestamps=timestamps)
    beam = max(1, int(beam))
    cache = kv_pool.lease(cfg, mel.shape[0] * beam,
                          len(prompt) + int(max_new))
    toks, nsp, cache = _generate_beam_jit(
        assets.params, jnp.asarray(mel), jnp.asarray(prompt, jnp.int32),
        jnp.asarray(sup), jnp.asarray(bsup), cache, beam=beam, **kwargs)
    # return the FINAL buffers to the pool: the leased input pages were
    # consumed functionally (same shape)
    kv_pool.release(cache)
    return toks, nsp


def detect_language(assets: WhisperAssets, mel: jnp.ndarray) -> str:
    """Single decoder step after <|sot|>, masked to language tokens
    (Whisper's language-id procedure); majority vote over windows."""
    st = assets.tokens
    if not st.language_ids:
        return "en"
    cfg = assets.cfg
    enc = encode(assets.params, jnp.asarray(mel), cfg)
    ckv = cross_kv(assets.params, enc, cfg)
    b = enc.shape[0]
    cache = StepCache.create(cfg, b, 1)
    logits, _ = decoder_step(assets.params,
                             jnp.full((b,), st.sot, jnp.int32),
                             jnp.int32(0), cache, ckv, cfg)
    lang_ids = np.array(sorted(st.language_ids.values()))
    sub = np.asarray(logits)[:, lang_ids]
    winners = lang_ids[sub.argmax(axis=1)]
    vote = np.bincount(winners).argmax()
    inv = {v: k for k, v in st.language_ids.items()}
    return inv[int(vote)]


# --------------------------------------------------------------------------
# Host-side parsing
# --------------------------------------------------------------------------

def parse_segments(tokens: np.ndarray, st: SpecialTokens, *,
                   window_s: float = 30.0) -> list[Segment]:
    """One window's token stream -> timed segments.

    Tolerant of malformed grammars (untrained models): text before the
    first timestamp lands at [0, window]; an unclosed trailing pair ends
    at the window boundary.
    """
    ts0 = st.timestamp_begin
    segs: list[Segment] = []
    cur_start: float | None = None
    cur: list[int] = []
    for t in tokens.tolist():
        if t == st.eot:
            break
        if t >= ts0:
            t_s = (t - ts0) * TIME_PRECISION
            if cur_start is None:
                if cur:        # leading text with no opening timestamp
                    segs.append(Segment(0.0, t_s, cur))
                    cur = []
                cur_start = t_s
            else:
                if cur:
                    segs.append(Segment(cur_start, t_s, cur))
                    cur = []
                    cur_start = None
                else:          # consecutive timestamps: new opening mark
                    cur_start = t_s
        else:
            cur.append(t)
    if cur:
        segs.append(Segment(cur_start if cur_start is not None else 0.0,
                            window_s, cur))
    return segs
