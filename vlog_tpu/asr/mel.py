"""Whisper log-mel frontend, bit-compatible with the reference pipeline.

The reference feeds faster-whisper, whose CTranslate2 frontend mirrors
OpenAI's ``log_mel_spectrogram`` (n_fft=400, hop=160, 80 slaney-scale mel
bins over 0..8kHz, log10 clamped to max-8, scaled (x+4)/4). We reproduce
those numerics in JAX so transcription quality is attributable to the
model weights, not frontend drift; tests oracle-check against
``transformers.WhisperFeatureExtractor`` to float tolerance.

TPU notes: framing is a gather, the DFT runs as ``jnp.fft.rfft`` (XLA
lowers FFT natively), and the mel projection is a (201, n_mels) matmul —
all batched over 30 s windows so long audio fills the MXU.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE_RATE = 16_000
N_FFT = 400
HOP_LENGTH = 160
CHUNK_LENGTH_S = 30
N_SAMPLES = SAMPLE_RATE * CHUNK_LENGTH_S      # 480_000
N_FRAMES = N_SAMPLES // HOP_LENGTH            # 3000


def _hz_to_mel_slaney(f: np.ndarray) -> np.ndarray:
    """Slaney mel scale: linear below 1 kHz, log above."""
    f = np.asarray(f, np.float64)
    mel = 3.0 * f / 200.0
    log_region = f >= 1000.0
    mel = np.where(
        log_region,
        15.0 + 27.0 * np.log(np.maximum(f, 1e-10) / 1000.0) / np.log(6.4),
        mel,
    )
    return mel


def _mel_to_hz_slaney(m: np.ndarray) -> np.ndarray:
    m = np.asarray(m, np.float64)
    f = 200.0 * m / 3.0
    log_region = m >= 15.0
    f = np.where(log_region, 1000.0 * np.exp(np.log(6.4) * (m - 15.0) / 27.0), f)
    return f


@lru_cache(maxsize=4)
def mel_filter_bank(n_mels: int = 80, n_fft: int = N_FFT,
                    sample_rate: int = SAMPLE_RATE,
                    fmax: float | None = None) -> np.ndarray:
    """(n_freq, n_mels) triangular slaney-normalized filterbank."""
    fmax = fmax if fmax is not None else sample_rate / 2.0
    n_freq = n_fft // 2 + 1
    freqs = np.linspace(0.0, sample_rate / 2.0, n_freq)
    mel_pts = np.linspace(_hz_to_mel_slaney(np.array(0.0)),
                          _hz_to_mel_slaney(np.array(fmax)), n_mels + 2)
    hz_pts = _mel_to_hz_slaney(mel_pts)
    fb = np.zeros((n_freq, n_mels), np.float64)
    for i in range(n_mels):
        lo, ctr, hi = hz_pts[i], hz_pts[i + 1], hz_pts[i + 2]
        up = (freqs - lo) / max(ctr - lo, 1e-10)
        down = (hi - freqs) / max(hi - ctr, 1e-10)
        fb[:, i] = np.maximum(0.0, np.minimum(up, down))
        fb[:, i] *= 2.0 / (hi - lo)           # slaney area normalization
    return fb.astype(np.float32)


@partial(jax.jit, static_argnames=("n_mels",))
@jax.named_scope("asr.mel")
def log_mel_spectrogram(audio: jnp.ndarray, *, n_mels: int = 80) -> jnp.ndarray:
    """(B, N_SAMPLES) float32 in [-1,1] -> (B, n_mels, N_FRAMES) features.

    Matches WhisperFeatureExtractor: reflect-padded centered STFT with a
    periodic Hann window, power spectrum, slaney mel projection,
    log10 clamped to (per-window max - 8), then (x + 4) / 4.
    """
    if audio.ndim == 1:
        audio = audio[None]
    b, n = audio.shape
    window = jnp.asarray(np.hanning(N_FFT + 1)[:-1].astype(np.float32))
    pad = N_FFT // 2
    x = jnp.pad(audio.astype(jnp.float32), ((0, 0), (pad, pad)), mode="reflect")
    n_frames_total = 1 + n // HOP_LENGTH      # 3001 for a full 30 s chunk
    idx = (np.arange(N_FFT)[None, :]
           + HOP_LENGTH * np.arange(n_frames_total)[:, None])
    frames = x[:, idx] * window               # (B, F, 400)
    spec = jnp.fft.rfft(frames, axis=-1)
    power = jnp.abs(spec[:, :-1, :]) ** 2     # drop the trailing frame
    fb = jnp.asarray(mel_filter_bank(n_mels))
    mel = power @ fb                          # (B, F-1, n_mels)
    log_spec = jnp.log10(jnp.maximum(mel, 1e-10))
    cap = jnp.max(log_spec, axis=(1, 2), keepdims=True) - 8.0
    log_spec = jnp.maximum(log_spec, cap)
    log_spec = (log_spec + 4.0) / 4.0
    return jnp.transpose(log_spec, (0, 2, 1))  # (B, n_mels, frames)


def pad_or_trim(audio: np.ndarray, length: int = N_SAMPLES) -> np.ndarray:
    """Whisper windows are exactly 30 s; zero-pad or cut the tail."""
    if audio.shape[-1] >= length:
        return audio[..., :length]
    pad = [(0, 0)] * (audio.ndim - 1) + [(0, length - audio.shape[-1])]
    return np.pad(audio, pad)
