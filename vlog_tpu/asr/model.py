"""Whisper encoder-decoder forward passes in functional JAX.

Weights load from the HuggingFace layout (vlog_tpu/asr/load.py) into a flat
``{hf_name: jnp.ndarray}`` dict; forward functions index it by name, so the
mapping is auditable 1:1 against ``transformers`` WhisperModel — the oracle
tests (tests/test_whisper_model.py) assert logit agreement with the torch
implementation under shared random weights.

Replaces the reference's CTranslate2 inference engine
(worker/transcription.py:78-111). Design is mesh-first: every function
takes a leading batch axis (30 s windows), so long-audio transcription
shards windows across devices (SURVEY §5) with ``jax.sharding`` —
no per-window Python loop.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class WhisperConfig:
    """The subset of HF WhisperConfig the forward pass needs."""

    d_model: int
    encoder_layers: int
    decoder_layers: int
    encoder_attention_heads: int
    decoder_attention_heads: int
    encoder_ffn_dim: int
    decoder_ffn_dim: int
    vocab_size: int
    num_mel_bins: int = 80
    max_source_positions: int = 1500
    max_target_positions: int = 448

    @classmethod
    def from_hf(cls, cfg: dict) -> "WhisperConfig":
        return cls(**{f: cfg[f] for f in (
            "d_model", "encoder_layers", "decoder_layers",
            "encoder_attention_heads", "decoder_attention_heads",
            "encoder_ffn_dim", "decoder_ffn_dim", "vocab_size",
            "num_mel_bins", "max_source_positions", "max_target_positions",
        )})


Params = dict[str, jnp.ndarray]


@dataclass(frozen=True)
class QuantTensor:
    """int8 per-output-channel weight: ``w ≈ q * scale[:, None]``.

    ``q`` is (out, in) int8, ``scale`` is (out,) float32. Stored in the
    params dict in place of the f32 ``*.weight``; :func:`_linear`
    dequantizes on use, so HBM traffic per matmul drops 4x while the
    accumulation stays f32 (PAPERS.md energy-efficient Whisper kernels).
    """

    q: jnp.ndarray
    scale: jnp.ndarray


jax.tree_util.register_dataclass(QuantTensor, ["q", "scale"], [])


def _linear(p: Params, name: str, x: jnp.ndarray) -> jnp.ndarray:
    """HF Linear: weight (out, in), optional bias.

    Quantized planes (asr/load.py ``quantize_params``) store the weight
    as a :class:`QuantTensor` (int8, dequant-on-use) or bf16 (cast at
    use); the matmul itself always accumulates in the activation dtype.
    """
    w = p[f"{name}.weight"]
    if isinstance(w, QuantTensor):
        y = (x @ w.q.T.astype(jnp.float32)) * w.scale
    else:
        if w.dtype != x.dtype:
            w = w.astype(x.dtype)
        y = x @ w.T
    b = p.get(f"{name}.bias")
    return y + b if b is not None else y


def _layer_norm(p: Params, name: str, x: jnp.ndarray) -> jnp.ndarray:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    xn = (x - mu) * jax.lax.rsqrt(var + 1e-5)
    return xn * p[f"{name}.weight"] + p[f"{name}.bias"]


def _split_heads(x: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    b, t, d = x.shape
    return x.reshape(b, t, n_heads, d // n_heads).transpose(0, 2, 1, 3)


def _merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    b, h, t, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * hd)


def _attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               mask: jnp.ndarray | None) -> jnp.ndarray:
    """(B,H,Tq,hd) x (B,H,Tk,hd); q pre-scaled (HF convention)."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def _self_attn(p: Params, name: str, x: jnp.ndarray, n_heads: int,
               mask: jnp.ndarray | None) -> jnp.ndarray:
    head_dim = x.shape[-1] // n_heads
    q = _linear(p, f"{name}.q_proj", x) * head_dim ** -0.5
    k = _linear(p, f"{name}.k_proj", x)       # k_proj has no bias in HF
    v = _linear(p, f"{name}.v_proj", x)
    out = _attention(_split_heads(q, n_heads), _split_heads(k, n_heads),
                     _split_heads(v, n_heads), mask)
    return _linear(p, f"{name}.out_proj", _merge_heads(out))


# --------------------------------------------------------------------------
# Encoder
# --------------------------------------------------------------------------

def _conv1d(p: Params, name: str, x: jnp.ndarray, stride: int) -> jnp.ndarray:
    """x: (B, C_in, T); HF Conv1d weight (C_out, C_in, K), pad 1."""
    y = jax.lax.conv_general_dilated(
        x, p[f"{name}.weight"], window_strides=(stride,), padding=[(1, 1)],
        dimension_numbers=("NCH", "OIH", "NCH"))
    return y + p[f"{name}.bias"][None, :, None]


@partial(jax.jit, static_argnames=("cfg",))
@jax.named_scope("asr.encoder")
def encode(params: Params, mel: jnp.ndarray, cfg: WhisperConfig) -> jnp.ndarray:
    """(B, n_mels, 3000) log-mel -> (B, 1500, d) encoder states."""
    p = params
    with jax.named_scope("asr.encoder.conv"):
        x = jax.nn.gelu(_conv1d(p, "model.encoder.conv1", mel, 1),
                        approximate=False)
        x = jax.nn.gelu(_conv1d(p, "model.encoder.conv2", x, 2),
                        approximate=False)
        x = x.transpose(0, 2, 1)                              # (B, T, d)
        x = x + p["model.encoder.embed_positions.weight"][: x.shape[1]]
    for i in range(cfg.encoder_layers):
        n = f"model.encoder.layers.{i}"
        with jax.named_scope("asr.encoder.attn"):
            h = _layer_norm(p, f"{n}.self_attn_layer_norm", x)
            x = x + _self_attn(p, f"{n}.self_attn", h,
                               cfg.encoder_attention_heads, None)
        with jax.named_scope("asr.encoder.mlp"):
            h = _layer_norm(p, f"{n}.final_layer_norm", x)
            h = jax.nn.gelu(_linear(p, f"{n}.fc1", h), approximate=False)
            x = x + _linear(p, f"{n}.fc2", h)
    return _layer_norm(p, "model.encoder.layer_norm", x)


# --------------------------------------------------------------------------
# Decoder (teacher-forced; the KV-cached incremental path is in decode.py)
# --------------------------------------------------------------------------

@jax.named_scope("asr.cross_kv")
def cross_kv(params: Params, enc: jnp.ndarray, cfg: WhisperConfig
             ) -> list[tuple[jnp.ndarray, jnp.ndarray]]:
    """Per-layer cross-attention K/V, computed once per audio window."""
    out = []
    for i in range(cfg.decoder_layers):
        n = f"model.decoder.layers.{i}.encoder_attn"
        k = _split_heads(_linear(params, f"{n}.k_proj", enc),
                         cfg.decoder_attention_heads)
        v = _split_heads(_linear(params, f"{n}.v_proj", enc),
                         cfg.decoder_attention_heads)
        out.append((k, v))
    return out


def _window_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray
                      ) -> jnp.ndarray:
    """Cross-attention of W windows x K beams over K/V kept by window:
    q (W*K,H,1,hd) pre-scaled, k/v (W,H,S,hd). The K queries of a window
    are one block contracted against that window's K/V in one product,
    so a step reads each K/V element once and no per-row copy is made.
    The contraction never leaves a window."""
    w = k.shape[0]
    rows, h, _, hd = q.shape
    q = q.reshape(w, rows // w, h, hd)
    scores = jnp.einsum("wqhd,whsd->whqs", q, k)
    out = jnp.einsum("whqs,whsd->wqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(rows, h, 1, hd)


def _cross_attn(p: Params, name: str, x: jnp.ndarray, kv, n_heads: int
                ) -> jnp.ndarray:
    """``kv`` is a layer's (K, V), each (B, H, S, hd) with one row per
    row of ``x`` (teacher forcing, ``detect_language``, a beam of one),
    or (W, H, S, hd) with W windows for W*K rows of one position each
    (the generate program at K > 1): the K rows of a window then share
    its K/V (:func:`_window_attention`)."""
    head_dim = x.shape[-1] // n_heads
    q = _split_heads(_linear(p, f"{name}.q_proj", x) * head_dim ** -0.5,
                     n_heads)
    out = (_attention(q, kv[0], kv[1], None)
           if kv[0].shape[0] == q.shape[0]
           else _window_attention(q, kv[0], kv[1]))
    return _linear(p, f"{name}.out_proj", _merge_heads(out))


@partial(jax.jit, static_argnames=("cfg",))
def decode_logits(params: Params, tokens: jnp.ndarray, enc: jnp.ndarray,
                  cfg: WhisperConfig) -> jnp.ndarray:
    """Teacher-forced full-sequence decoder: (B, L) tokens -> (B, L, V).

    Used by the oracle tests and for scoring; the generation loop uses the
    cached incremental step (decode.py) instead.
    """
    p = params
    b, L = tokens.shape
    x = (p["model.decoder.embed_tokens.weight"][tokens]
         + p["model.decoder.embed_positions.weight"][:L])
    causal = jnp.tril(jnp.ones((L, L), bool))[None, None]
    ckv = cross_kv(params, enc, cfg)
    for i in range(cfg.decoder_layers):
        n = f"model.decoder.layers.{i}"
        h = _layer_norm(p, f"{n}.self_attn_layer_norm", x)
        x = x + _self_attn(p, f"{n}.self_attn", h,
                           cfg.decoder_attention_heads, causal)
        h = _layer_norm(p, f"{n}.encoder_attn_layer_norm", x)
        x = x + _cross_attn(p, f"{n}.encoder_attn", h, ckv[i],
                            cfg.decoder_attention_heads)
        h = _layer_norm(p, f"{n}.final_layer_norm", x)
        h = jax.nn.gelu(_linear(p, f"{n}.fc1", h), approximate=False)
        x = x + _linear(p, f"{n}.fc2", h)
    x = _layer_norm(p, "model.decoder.layer_norm", x)
    return x @ p["model.decoder.embed_tokens.weight"].T


# --------------------------------------------------------------------------
# Incremental decoder step with static-shape KV cache (generation hot path)
# --------------------------------------------------------------------------

@dataclass
class DecoderCache:
    """A self-attention K/V page, (layers, B, H, max_len, hd): what the
    pool keeps and the generate program takes and returns."""

    k: jnp.ndarray
    v: jnp.ndarray

    @classmethod
    def create(cls, cfg: WhisperConfig, batch: int, max_len: int,
               dtype=jnp.float32) -> "DecoderCache":
        hd = cfg.d_model // cfg.decoder_attention_heads
        shape = (cfg.decoder_layers, batch, cfg.decoder_attention_heads,
                 max_len, hd)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


jax.tree_util.register_dataclass(DecoderCache, ["k", "v"], [])


@dataclass
class StepCache:
    """Self-attention K/V as :func:`decoder_step` reads and writes it,
    (layers, B, max_len, d_model): a row's position is the K (or V)
    projection as it comes, the heads side by side along ``d_model``,
    so a step's new entry for a layer is one ``(B, 1, d_model)`` slab.
    """

    k: jnp.ndarray
    v: jnp.ndarray

    @classmethod
    def create(cls, cfg: WhisperConfig, batch: int, max_len: int
               ) -> "StepCache":
        shape = (cfg.decoder_layers, batch, max_len, cfg.d_model)
        return cls(k=jnp.zeros(shape), v=jnp.zeros(shape))

    @classmethod
    def from_page(cls, page: DecoderCache) -> "StepCache":
        merge = jax.vmap(_merge_heads)
        return cls(k=merge(page.k), v=merge(page.v))

    def to_page(self, n_heads: int) -> DecoderCache:
        split = jax.vmap(partial(_split_heads, n_heads=n_heads))
        return DecoderCache(k=split(self.k), v=split(self.v))


jax.tree_util.register_dataclass(StepCache, ["k", "v"], [])


def _beam_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    anc: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Self-attention of W windows x K beams over a cache nobody
    reorders: q (W*K,H,1,hd) pre-scaled, k/v (W*K,T,d_model) a layer of
    a :class:`StepCache`, ``anc`` (W,K,T) with ``anc[w, q, t]`` the slot
    of window ``w`` that holds position ``t`` of beam ``q``'s history,
    ``valid`` (T,) the written positions.

    Every query is scored against all K slots of its OWN window and the
    mask keeps, per valid position, the one slot its ancestry names; the
    softmax runs over the flattened (slot, position) axis, so the sum is
    over the same entries a per-row attention over a gathered cache
    sees. The contraction never leaves a window."""
    w, kb, t = anc.shape
    _, h, _, hd = q.shape
    q = q.reshape(w, kb, h, hd)
    k = k.reshape(w, kb, t, h, hd)
    v = v.reshape(w, kb, t, h, hd)
    scores = jnp.einsum("bqhd,bsthd->bhqst", q, k)
    mask = ((anc[:, :, None, :] == jnp.arange(kb)[None, None, :, None])
            & valid)[:, None]                           # (W,1,Kq,Ks,T)
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.reshape(w, h, kb, kb * t), axis=-1)
    out = jnp.einsum("bhqst,bsthd->bqhd", probs.reshape(scores.shape), v)
    return out.reshape(w * kb, h, 1, hd)


@jax.named_scope("asr.decoder_step")
def decoder_step(params: Params, tokens: jnp.ndarray, pos: jnp.ndarray,
                 cache: StepCache, ckv, cfg: WhisperConfig,
                 anc: jnp.ndarray | None = None
                 ) -> tuple[jnp.ndarray, StepCache]:
    """One decode step: (B,) tokens at position ``pos`` -> (B, V) logits.

    XLA-friendly: every shape is static; each layer writes its ONE new
    K/V position, the projection's (B, 1, d_model) output as it comes,
    into the stacked (layers, B, max_len, d_model) arrays in place (a
    dynamic_update_slice at ``[i, :, pos, :]``; nothing is sliced out,
    updated and restacked) and attention masks positions > pos. With
    ``anc`` (W, K, max_len), B is W windows x K beams and a row attends
    through its ancestry table over its window's K cache slots
    (:func:`_beam_attention`); without it (the generate program's
    prompt steps, ``detect_language``) each row attends over its own
    cache row. ``ckv`` is :func:`cross_kv`'s list, a (K, V) pair per
    layer of (rows, H, source, hd) each: B rows, one per query row
    (``detect_language``, a beam of one), or W rows for B = W x K, the
    K beams of a window reading its K/V together (:func:`_cross_attn`
    tells the two apart by the row count; nothing is tiled).
    """
    p = params
    nh = cfg.decoder_attention_heads
    hd = cfg.d_model // nh
    max_len = cache.k.shape[2]
    x = (p["model.decoder.embed_tokens.weight"][tokens]
         + p["model.decoder.embed_positions.weight"][pos])[:, None, :]
    ck, cv = cache.k, cache.v
    valid = jnp.arange(max_len) <= pos      # written positions, (max_len,)
    for i in range(cfg.decoder_layers):
        n = f"model.decoder.layers.{i}"
        with jax.named_scope("asr.decoder_step.self_attn"):
            h = _layer_norm(p, f"{n}.self_attn_layer_norm", x)
            q = (_linear(p, f"{n}.self_attn.q_proj", h) * hd ** -0.5)
            k1 = _linear(p, f"{n}.self_attn.k_proj", h)
            v1 = _linear(p, f"{n}.self_attn.v_proj", h)
        with jax.named_scope("asr.decoder_step.cache_update"):
            ck = jax.lax.dynamic_update_slice(ck, k1[None], (i, 0, pos, 0))
            cv = jax.lax.dynamic_update_slice(cv, v1[None], (i, 0, pos, 0))
        with jax.named_scope("asr.decoder_step.self_attn"):
            qh = _split_heads(q, nh)
            att = (_attention(qh, _split_heads(ck[i], nh),
                              _split_heads(cv[i], nh), valid)
                   if anc is None
                   else _beam_attention(qh, ck[i], cv[i], anc, valid))
            x = x + _linear(p, f"{n}.self_attn.out_proj", _merge_heads(att))
        with jax.named_scope("asr.decoder_step.cross_attn"):
            h = _layer_norm(p, f"{n}.encoder_attn_layer_norm", x)
            x = x + _cross_attn(p, f"{n}.encoder_attn", h, ckv[i], nh)
        with jax.named_scope("asr.decoder_step.mlp"):
            h = _layer_norm(p, f"{n}.final_layer_norm", x)
            h = jax.nn.gelu(_linear(p, f"{n}.fc1", h), approximate=False)
            x = x + _linear(p, f"{n}.fc2", h)
    with jax.named_scope("asr.decoder_step.logits"):
        x = _layer_norm(p, "model.decoder.layer_norm", x)
        logits = (x @ p["model.decoder.embed_tokens.weight"].T)[:, 0, :]
    return logits, StepCache(k=ck, v=cv)


def random_state_dict(cfg: WhisperConfig, seed: int = 0
                      ) -> dict[str, np.ndarray]:
    """Seeded random weights for ``cfg`` as a host state dict in the HF
    naming scheme — what ``asr/load.py`` reads back from
    ``model.safetensors`` (tests, and chip_smoke.py's checkpoint)."""
    rng = np.random.default_rng(seed)
    p: dict[str, np.ndarray] = {}

    def w(name, *shape, scale=0.02):
        p[name] = (rng.standard_normal(shape) * scale).astype(np.float32)

    def ln(name):
        p[f"{name}.weight"] = np.ones(cfg.d_model, np.float32)
        p[f"{name}.bias"] = np.zeros(cfg.d_model, np.float32)

    d = cfg.d_model
    w("model.encoder.conv1.weight", d, cfg.num_mel_bins, 3)
    w("model.encoder.conv1.bias", d)
    w("model.encoder.conv2.weight", d, d, 3)
    w("model.encoder.conv2.bias", d)
    w("model.encoder.embed_positions.weight", cfg.max_source_positions, d)
    w("model.decoder.embed_tokens.weight", cfg.vocab_size, d)
    w("model.decoder.embed_positions.weight", cfg.max_target_positions, d)
    ln("model.encoder.layer_norm")
    ln("model.decoder.layer_norm")
    for side, nl, ffn in (("encoder", cfg.encoder_layers, cfg.encoder_ffn_dim),
                          ("decoder", cfg.decoder_layers, cfg.decoder_ffn_dim)):
        for i in range(nl):
            n = f"model.{side}.layers.{i}"
            attns = ["self_attn"] if side == "encoder" else [
                "self_attn", "encoder_attn"]
            for a in attns:
                w(f"{n}.{a}.q_proj.weight", d, d)
                w(f"{n}.{a}.q_proj.bias", d)
                w(f"{n}.{a}.k_proj.weight", d, d)
                w(f"{n}.{a}.v_proj.weight", d, d)
                w(f"{n}.{a}.v_proj.bias", d)
                w(f"{n}.{a}.out_proj.weight", d, d)
                w(f"{n}.{a}.out_proj.bias", d)
                ln(f"{n}.{a}_layer_norm")
            w(f"{n}.fc1.weight", ffn, d)
            w(f"{n}.fc1.bias", ffn)
            w(f"{n}.fc2.weight", d, ffn)
            w(f"{n}.fc2.bias", d)
            ln(f"{n}.final_layer_norm")
    return p


def init_random_params(cfg: WhisperConfig, seed: int = 0) -> Params:
    """:func:`random_state_dict` as device params (tests only)."""
    return {k: jnp.asarray(v)
            for k, v in random_state_dict(cfg, seed).items()}
