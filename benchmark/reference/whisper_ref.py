"""Plain Whisper reference: what the published model computes, in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision.

No kernels, no cache, no batching tricks, and **nothing imported from
the program**: the log-mel front end is an explicit DFT (two matrix
products, not XLA's FFT), the encoder and the teacher-forced decoder
follow openai/whisper (pre-norm blocks, exact GELU, k_proj without
bias, tied output embedding, layer-norm eps 1e-5), and the generation
rules (suppress lists, the timestamp grammar, no-speech) and the
token -> cue -> WebVTT steps are written out on the host in NumPy and
plain Python. Departures from the published model: weights are random
from the seed (``models/whisper_weights.py``) and the encoder's
position table is one of them rather than the fixed sinusoids.

``dtype=jnp.bfloat16`` computes the same forward with weights AND
activations in bfloat16 at default precision: the control of "How
correct is decided" (the nearest precision below the configuration's
float32). The benchmark's own runs never use it; ``control.py`` and the
tests do.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE_RATE = 16_000
N_FFT = 400
HOP = 160
N_SAMPLES = 30 * SAMPLE_RATE
N_FRAMES = N_SAMPLES // HOP
TIME_PRECISION = 0.02
MAX_INITIAL_TIMESTAMP_INDEX = 50
LN_EPS = 1e-5
NO_SPEECH_THRESHOLD = 0.6


# --------------------------------------------------------------------------
# Front end
# --------------------------------------------------------------------------

def _hz_to_mel(f):
    f = np.asarray(f, np.float64)
    lin = 3.0 * f / 200.0
    log = 15.0 + 27.0 * np.log(np.maximum(f, 1e-10) / 1000.0) / np.log(6.4)
    return np.where(f >= 1000.0, log, lin)


def _mel_to_hz(m):
    m = np.asarray(m, np.float64)
    lin = 200.0 * m / 3.0
    log = 1000.0 * np.exp(np.log(6.4) * (m - 15.0) / 27.0)
    return np.where(m >= 15.0, log, lin)


def mel_filters(n_mels: int) -> np.ndarray:
    """(201, n_mels) slaney-scale, slaney-normalised triangles over
    0..8 kHz (librosa's ``mel(sr=16000, n_fft=400, n_mels=n_mels)``,
    which openai/whisper ships as ``mel_filters.npz``)."""
    n_freq = N_FFT // 2 + 1
    freqs = np.linspace(0.0, SAMPLE_RATE / 2.0, n_freq)
    pts = _mel_to_hz(np.linspace(_hz_to_mel(0.0),
                                 _hz_to_mel(SAMPLE_RATE / 2.0), n_mels + 2))
    fb = np.zeros((n_freq, n_mels), np.float64)
    for i in range(n_mels):
        lo, mid, hi = pts[i], pts[i + 1], pts[i + 2]
        tri = np.minimum((freqs - lo) / (mid - lo), (hi - freqs) / (hi - mid))
        fb[:, i] = np.maximum(tri, 0.0) * 2.0 / (hi - lo)
    return fb.astype(np.float32)


def _dft_matrices() -> tuple[np.ndarray, np.ndarray]:
    n = np.arange(N_FFT)[:, None]
    k = np.arange(N_FFT // 2 + 1)[None, :]
    ang = 2.0 * np.pi * n * k / N_FFT
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


@partial(jax.jit, static_argnames=("n_mels",))
def log_mel(audio, *, n_mels: int):
    """(B, 480000) float32 -> (B, n_mels, 3000): centred reflect-padded
    STFT with a periodic Hann window, power, mel, log10 clamped to
    (max - 8), (x + 4) / 4."""
    with jax.default_matmul_precision("highest"):
        window = jnp.asarray(
            (0.5 - 0.5 * np.cos(2 * np.pi * np.arange(N_FFT) / N_FFT)
             ).astype(np.float32))
        x = jnp.pad(audio, ((0, 0), (N_FFT // 2, N_FFT // 2)),
                    mode="reflect")
        idx = np.arange(N_FFT)[None, :] + HOP * np.arange(N_FRAMES)[:, None]
        frames = x[:, idx] * window                       # (B, 3000, 400)
        cos, sin = _dft_matrices()
        re_ = frames @ jnp.asarray(cos)
        im_ = frames @ jnp.asarray(sin)
        power = re_ * re_ + im_ * im_                     # (B, 3000, 201)
        mel = power @ jnp.asarray(mel_filters(n_mels))
        log_spec = jnp.log10(jnp.maximum(mel, 1e-10))
        log_spec = jnp.maximum(
            log_spec, jnp.max(log_spec, axis=(1, 2), keepdims=True) - 8.0)
        return jnp.transpose((log_spec + 4.0) / 4.0, (0, 2, 1))


def pad_or_trim(audio: np.ndarray) -> np.ndarray:
    out = np.zeros(N_SAMPLES, np.float32)
    n = min(N_SAMPLES, audio.shape[-1])
    out[:n] = audio[:n]
    return out


# --------------------------------------------------------------------------
# Model
# --------------------------------------------------------------------------

def _linear(p, name, x):
    y = x @ p[f"{name}.weight"].T
    b = p.get(f"{name}.bias")
    return y if b is None else y + b


def _ln(p, name, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + LN_EPS)) * p[f"{name}.weight"] \
        + p[f"{name}.bias"]


def _heads(x, n):
    b, t, d = x.shape
    return x.reshape(b, t, n, d // n).transpose(0, 2, 1, 3)


def _attend(p, name, xq, xkv, n_heads, mask):
    hd = xq.shape[-1] // n_heads
    q = _heads(_linear(p, f"{name}.q_proj", xq) * hd ** -0.5, n_heads)
    k = _heads(_linear(p, f"{name}.k_proj", xkv), n_heads)
    v = _heads(_linear(p, f"{name}.v_proj", xkv), n_heads)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", a, v)
    b, h, t, _ = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, t, h * hd)
    return _linear(p, f"{name}.out_proj", o)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x * 0.7071067811865476))


def _mlp(p, n, x):
    return _linear(p, f"{n}.fc2", _gelu(_linear(p, f"{n}.fc1", x)))


def _conv(p, name, x, stride):
    y = jax.lax.conv_general_dilated(
        x, p[f"{name}.weight"], (stride,), [(1, 1)],
        dimension_numbers=("NCH", "OIH", "NCH"))
    return y + p[f"{name}.bias"][None, :, None]


def _forward(p, mel, tokens, *, n_enc, n_dec, n_heads):
    """mel (B, n_mels, 3000), tokens (B, L) -> logits (B, L, V)."""
    x = _gelu(_conv(p, "model.encoder.conv1", mel, 1))
    x = _gelu(_conv(p, "model.encoder.conv2", x, 2)).transpose(0, 2, 1)
    x = x + p["model.encoder.embed_positions.weight"][: x.shape[1]]
    for i in range(n_enc):
        n = f"model.encoder.layers.{i}"
        h = _ln(p, f"{n}.self_attn_layer_norm", x)
        x = x + _attend(p, f"{n}.self_attn", h, h, n_heads, None)
        x = x + _mlp(p, n, _ln(p, f"{n}.final_layer_norm", x))
    enc = _ln(p, "model.encoder.layer_norm", x)

    length = tokens.shape[1]
    y = (p["model.decoder.embed_tokens.weight"][tokens]
         + p["model.decoder.embed_positions.weight"][:length])
    causal = jnp.tril(jnp.ones((length, length), bool))[None, None]
    for i in range(n_dec):
        n = f"model.decoder.layers.{i}"
        h = _ln(p, f"{n}.self_attn_layer_norm", y)
        y = y + _attend(p, f"{n}.self_attn", h, h, n_heads, causal)
        h = _ln(p, f"{n}.encoder_attn_layer_norm", y)
        y = y + _attend(p, f"{n}.encoder_attn", h, enc, n_heads, None)
        y = y + _mlp(p, n, _ln(p, f"{n}.final_layer_norm", y))
    y = _ln(p, "model.decoder.layer_norm", y)
    return y @ p["model.decoder.embed_tokens.weight"].T


@partial(jax.jit, static_argnames=("n_enc", "n_dec", "n_heads"))
def _forward_f32(p, mel, tokens, *, n_enc, n_dec, n_heads):
    with jax.default_matmul_precision("highest"):
        return _forward(p, mel, tokens, n_enc=n_enc, n_dec=n_dec,
                        n_heads=n_heads)


@partial(jax.jit, static_argnames=("n_enc", "n_dec", "n_heads"))
def _forward_bf16(p, mel, tokens, *, n_enc, n_dec, n_heads):
    p = {k: v.astype(jnp.bfloat16) for k, v in p.items()}
    out = _forward(p, mel.astype(jnp.bfloat16), tokens, n_enc=n_enc,
                   n_dec=n_dec, n_heads=n_heads)
    return out.astype(jnp.float32)


def logits(params, cfg: dict, mel, tokens, *, dtype=jnp.float32,
           block: int = 4) -> np.ndarray:
    """Teacher-forced logits for every position, computed ``block``
    windows at a time so that the (block, L, vocab) array fits beside
    the weights. Returns float32 (N, L, V) on the host."""
    fn = _forward_f32 if dtype == jnp.float32 else _forward_bf16
    kw = dict(n_enc=cfg["encoder_layers"], n_dec=cfg["decoder_layers"],
              n_heads=cfg["decoder_attention_heads"])
    n = mel.shape[0]
    out = []
    for b0 in range(0, n, block):
        m = mel[b0:b0 + block]
        t = np.asarray(tokens[b0:b0 + block], np.int32)
        pad = block - m.shape[0]
        if pad:      # one compiled shape: pad the last block with copies
            m = jnp.concatenate([m, jnp.repeat(m[-1:], pad, axis=0)])
            t = np.concatenate([t, np.repeat(t[-1:], pad, axis=0)])
        lg = np.asarray(fn(params, m, jnp.asarray(t), **kw))
        out.append(lg[:block - pad])
    return np.concatenate(out)


# --------------------------------------------------------------------------
# Generation rules (host, one position at a time)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Vocab:
    """Ids that steer generation (published multilingual layout)."""

    sot: int
    eot: int
    transcribe: int
    no_timestamps: int
    timestamp_begin: int
    no_speech: int
    language: int
    suppress: tuple[int, ...]
    begin_suppress: tuple[int, ...]

    @property
    def prompt(self) -> list[int]:
        return [self.sot, self.language, self.transcribe]


def allowed_mask(prefix: list[int], step: int, lg: np.ndarray, v: Vocab,
                 force_ts: bool | None = None
                 ) -> tuple[np.ndarray, float]:
    """Which ids may follow ``prefix`` (the tokens generated so far) at
    generated step ``step`` under Whisper's rules, and the margin of the
    probability rule (log mass on timestamps minus the best text token's
    log-probability; positive forces a timestamp). ``force_ts`` overrides
    the rule's own decision."""
    n = lg.shape[0]
    ids = np.arange(n)
    is_ts = ids >= v.timestamp_begin
    ok = np.ones(n, bool)
    ok[list(v.suppress) + [v.no_timestamps]] = False
    if step == 0:
        ok[[i for i in v.begin_suppress if i < n]] = False
    last = prefix[-1] if prefix else -1
    penult = prefix[-2] if len(prefix) >= 2 else -1
    lw = last >= v.timestamp_begin
    pw = penult >= v.timestamp_begin
    if lw and pw:
        ok &= ~is_ts
    elif lw:
        ok &= is_ts | (ids == v.eot)
    seen = [t for t in prefix if t >= v.timestamp_begin]
    if seen:
        cutoff = seen[-1] if (lw and not pw) else seen[-1] + 1
    else:
        cutoff = v.timestamp_begin
    ok &= ~(is_ts & (ids < cutoff))
    if step == 0:
        bad = (~is_ts) | (ids > v.timestamp_begin
                          + MAX_INITIAL_TIMESTAMP_INDEX)
        ok &= ~(bad & (ids != v.eot))
    masked = np.where(ok, lg.astype(np.float64), -np.inf)
    if not np.isfinite(masked).any():
        return ok, 0.0
    lse = np.logaddexp.reduce(masked)
    ts = masked[is_ts]
    txt = masked[~is_ts]
    ts_lp = np.logaddexp.reduce(ts) - lse if np.isfinite(ts).any() \
        else -np.inf
    txt_max = txt.max() - lse if np.isfinite(txt).any() else -np.inf
    margin = float(ts_lp - txt_max) if (np.isfinite(ts_lp)
                                        or np.isfinite(txt_max)) else 0.0
    force = margin > 0 if force_ts is None else force_ts
    if force and np.isfinite(ts_lp):
        ok = ok & is_ts
    return ok, margin


def rank_gap(served: int, lg: np.ndarray, ok: np.ndarray, k: int) -> float:
    """How far the served token's logit lies below the ``k``-th best
    allowed logit (0 when it is among the best ``k``). Beam search with
    ``k`` beams can only keep a continuation that is among the best ``k``
    of its own parent, so a sound served token reads 0 up to rounding;
    a token the rules forbid reads ``inf``."""
    if not ok[served]:
        return float("inf")
    allowed = lg[ok]
    if allowed.size <= k:
        return 0.0
    kth = np.partition(allowed, -k)[-k]
    return float(max(0.0, kth - lg[served]))


def served_gaps(lg: np.ndarray, served: np.ndarray, v: Vocab, k: int, *,
                rule_tol: float) -> list[float]:
    """One window: ``lg`` (L, V) teacher-forced logits over the prompt
    and the served tokens, ``served`` the generated tokens. Returns the
    rank gap of every served token up to and including the first EOT.
    Where the probability rule's margin is within ``rule_tol`` of 0 the
    rule could fall either way on rounding, and the smaller gap counts.
    """
    plen = len(v.prompt)
    gaps = []
    prefix: list[int] = []
    for step, tok in enumerate(served.tolist()):
        row = lg[plen - 1 + step]
        ok, margin = allowed_mask(prefix, step, row, v)
        g = rank_gap(tok, row, ok, k)
        if abs(margin) < rule_tol and g > 0:
            ok2, _ = allowed_mask(prefix, step, row, v,
                                  force_ts=not margin > 0)
            g = min(g, rank_gap(tok, row, ok2, k))
        gaps.append(g)
        prefix.append(tok)
        if tok == v.eot:
            break
    return gaps


def first_choice_gaps(lg_ref: np.ndarray, lg_low: np.ndarray,
                      served: np.ndarray, v: Vocab) -> list[float]:
    """The control's reading: at every position of the same prompt and
    served tokens, how far the token that the lower precision puts first
    lies below the reference's best, both under the reference's rules."""
    plen = len(v.prompt)
    gaps = []
    prefix: list[int] = []
    for step, tok in enumerate(served.tolist()):
        ok, _ = allowed_mask(prefix, step, lg_ref[plen - 1 + step], v)
        ref = np.where(ok, lg_ref[plen - 1 + step], -np.inf)
        low = np.where(ok, lg_low[plen - 1 + step], -np.inf)
        gaps.append(float(ref.max() - ref[int(low.argmax())]))
        prefix.append(tok)
        if tok == v.eot:
            break
    return gaps


def no_speech_logp(lg: np.ndarray, v: Vocab) -> float:
    """log P(<|nospeech|>) from the distribution after the prompt."""
    row = lg[len(v.prompt) - 1].astype(np.float64)
    return float(row[v.no_speech] - np.logaddexp.reduce(row))


# --------------------------------------------------------------------------
# Tokens -> cues -> WebVTT (host)
# --------------------------------------------------------------------------

def decode_text(ids: list[int]) -> str:
    """The tokenizer stand-in both sides use: one word per text id."""
    return "".join(f" w{i}" for i in ids)


def parse_cues(tokens: list[int], v: Vocab, *, start_s: float,
               window_s: float = 30.0) -> list[tuple[float, float, str]]:
    """One window's served tokens -> ``(start, end, text)`` in track
    time. Timestamps come in closing/opening pairs; text before the
    first timestamp starts at 0, an unclosed tail ends at the window."""
    cues = []
    open_at: float | None = None
    words: list[int] = []

    def emit(a, b):
        text = decode_text([t for t in words if t < v.sot])
        cues.append((start_s + a, start_s + b, text))

    for t in tokens:
        if t == v.eot:
            break
        if t >= v.timestamp_begin:
            at = (t - v.timestamp_begin) * TIME_PRECISION
            if open_at is None:
                if words:
                    emit(0.0, at)
                    words = []
                open_at = at
            elif words:
                emit(open_at, at)
                words = []
                open_at = None
            else:
                open_at = at
        else:
            words.append(t)
    if words:
        emit(open_at if open_at is not None else 0.0, window_s)
    return cues


_WS = re.compile(r"\s+")


def stitch(per_window: list[list[tuple[float, float, str]]]
           ) -> list[tuple[float, float, str]]:
    """Windows overlap by 5 s: a cue that ends inside what was already
    emitted (0.2 s of grace) is dropped, one that straddles is clamped."""
    out = []
    until = 0.0
    for cues in per_window:
        for a, b, text in sorted(cues, key=lambda c: (c[0], c[1])):
            text = _WS.sub(" ", text).strip()
            if not text or b <= until + 0.2:
                continue
            out.append((max(a, until), b, text))
            until = b
    return out


def _stamp(t: float) -> str:
    t = max(0.0, t)
    return f"{int(t // 3600):02d}:{int(t % 3600 // 60):02d}:{t % 60:06.3f}"


def vtt(cues: list[tuple[float, float, str]]) -> str:
    lines = ["WEBVTT", ""]
    for a, b, text in cues:
        text = text.strip()
        if not text:
            continue
        text = (text.replace("&", "&amp;").replace("<", "&lt;")
                .replace(">", "&gt;"))
        lines += [f"{_stamp(a)} --> {_stamp(max(b, a))}", text, ""]
    return "\n".join(lines) + ("\n" if lines[-1] else "")
