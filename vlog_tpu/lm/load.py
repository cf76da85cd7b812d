"""A transcript model on disk: ``config.json`` (the published keys of a
family ``LmConfig.from_hf`` builds: ``afmoe``, ``KeyeVL2``'s language
model, ``xing4_0`` or ``qwen3_next``; ``model_type`` says which), the weights as safetensors (one ``model.safetensors``, or the
shards that ``model.safetensors.index.json`` names; the published names,
torch layouts) and ``tokenizer.json``. Nothing is fetched: the operator
points ``VLOG_DIGEST_DIR`` at a local directory, as ``VLOG_WHISPER_DIR``.
A directory that lacks one of the three is refused (``LmLoadError``).

The program's own layout (``model.py`` indexes it) is a nested dict:
``embed`` (V, H), ``head`` (H, V), ``final_norm``, and per layer ``n1``
.. ``n4``, ``wq`` ``wk`` ``wv`` ``wg`` (H, out), ``wo``, ``qn`` ``kn``,
then ``w_gate`` ``w_up`` ``w_down`` (dense) or ``router`` (H, E),
``bias`` (E,), ``e_gate`` ``e_up`` (E, H, I), ``e_down`` (E, I, H) and
``s_gate`` ``s_up`` ``s_down`` (the shared expert). Everything bfloat16
except ``bias`` (float32). A ``KeyeVL2`` layer has ``n1`` ``n2``, ``wq``
``wk`` ``wv`` ``wo``, ``qn`` ``kn``, the indexer's ``iq`` (H, heads x
dim), ``ik`` (H, dim), ``ikn`` ``ikb`` (its key's LayerNorm) and ``iw``
(H, heads), then ``router`` and the three expert stacks: no gate, no
bias, no dense layer, no shared expert. A ``xing4_0`` layer has ``n1``
``n2``, the latent attention's ``wqa`` (H, q rank), ``qan``, ``wqb`` (q
rank, heads x (nope + rope)), ``wkva`` (H, kv rank + rope), ``kvn``,
``wkvb`` (kv rank, heads x (nope + v)), ``wo``, one hyper-connection a
sublayer, ``hca_*`` (attention) and ``hcm_*`` (MLP): ``_w`` (streams x H,
2 streams + streams^2) bfloat16, ``_b`` (the same width) and ``_a`` (3,)
float32, then the dense or the expert leaves of ``afmoe``. A
``qwen3_next`` layer has ``n1`` ``n2`` (zero-centred: the factor is ``1 +
w``), then a DeltaNet's ``w_qkvz`` (H, key heads x (2 dk + 2 r dv)),
``w_ba`` (H, 2 value heads), ``conv`` (taps, conv channels), ``a_log``
``dt_bias`` (value heads,) float32, ``norm`` (dv,) and ``w_out``, or a
gated attention's ``wq`` (H, heads x 2 hd: ``[q | gate]`` a head),
``wk`` ``wv`` ``wo`` ``qn`` ``kn``; then ``router`` (H, the router's
width), the held experts' stacks, the shared expert ``s_*`` and its gate
``sg`` (H, 1).

No published checkpoint's index has been met for any family (this
machine has no network): the names below are the published modelling
code's for ``afmoe``; for ``KeyeVL2``, those of the family its config
descends from with the indexer's as the published sparse attention
names them; for ``xing4_0``, those of the latent-attention family its
config descends from, and names of this repository's choosing for the
hyper-connections' tensors (``self_attn_hc.*``, ``mlp_hc.*``), which no
catalog row names; for ``qwen3_next``, the published modelling code's
(``linear_attn.*``, ``mlp.shared_expert_gate``; the conv's weight is
stored ``(channels, 1, taps)``) with the experts held here read by their
published index; a checkpoint that names a tensor otherwise is
refused by that name (``LmLoadError``), never half loaded.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any

import jax.numpy as jnp

from vlog_tpu.lm.model import BF16, F32, LINEAR, QWEN3_NEXT, XING, LmConfig


class LmLoadError(RuntimeError):
    pass


class _HfTokenizer:
    def __init__(self, path: Path):
        from tokenizers import Tokenizer

        self._tok = Tokenizer.from_file(str(path))

    def encode(self, text: str) -> list[int]:
        return list(self._tok.encode(text, add_special_tokens=False).ids)

    def decode(self, ids) -> str:
        return self._tok.decode(list(ids), skip_special_tokens=True)


@dataclass
class LmAssets:
    cfg: LmConfig
    params: dict
    tokenizer: Any
    model_name: str
    eos_id: int | None = None


def layer_leaves(cfg: LmConfig, li: int) -> list[tuple[str, tuple, str]]:
    """``(our key, shape, kind)`` of one layer's leaves; ``kind`` is
    ``normal``, ``ones``, ``zeros``, ``bias`` (float32, as every kind
    that begins so: ``bias_zeros``, ``bias_ones``) or ``hc`` (a
    hyper-connection's projection)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    if cfg.linear_layers:
        norms = [("n1", (h,), "zeros"), ("n2", (h,), "zeros")]
        if cfg.layer_types[li] == LINEAR:
            nk, nv = cfg.linear_key_heads, cfg.linear_value_heads
            dk, dv = cfg.linear_key_dim, cfg.linear_value_dim
            mixer = [("w_qkvz", (h, 2 * nk * dk + 2 * nv * dv), "normal"),
                     ("w_ba", (h, 2 * nv), "normal"),
                     ("conv", (cfg.linear_conv, cfg.conv_dim), "normal"),
                     ("a_log", (nv,), "bias"), ("dt_bias", (nv,), "bias_ones"),
                     ("norm", (dv,), "ones"), ("w_out", (nv * dv, h), "normal")]
        else:
            mixer = [("wq", (h, 2 * q), "normal"), ("wk", (h, kv), "normal"),
                     ("wv", (h, kv), "normal"), ("wo", (q, h), "normal"),
                     ("qn", (hd,), "zeros"), ("kn", (hd,), "zeros")]
        e, i = cfg.num_experts, cfg.moe_intermediate_size
        s = i * cfg.num_shared_experts
        return norms + mixer + [
            ("router", (h, cfg.router_experts), "normal"),
            ("e_gate", (e, h, i), "normal"), ("e_up", (e, h, i), "normal"),
            ("e_down", (e, i, h), "normal"), ("s_gate", (h, s), "normal"),
            ("s_up", (h, s), "normal"), ("s_down", (s, h), "normal"),
            ("sg", (h, 1), "normal")]
    if cfg.latent_width:
        nh, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        maps = 2 * cfg.hc_mult + cfg.hc_mult ** 2
        hc = [(f"hc{s}_{part}", shape, kind) for s in "am"
              for part, shape, kind in (
                  ("w", (cfg.hc_mult * h, maps), "hc"),
                  ("b", (maps,), "bias_zeros"), ("a", (3,), "bias_ones"))]
        return [("n1", (h,), "ones"), ("n2", (h,), "ones"),
                ("wqa", (h, cfg.q_lora_rank), "normal"),
                ("qan", (cfg.q_lora_rank,), "ones"),
                ("wqb", (cfg.q_lora_rank, q), "normal"),
                ("wkva", (h, cfg.latent_width), "normal"),
                ("kvn", (rank,), "ones"),
                ("wkvb", (rank, nh * (cfg.qk_nope_head_dim
                                      + cfg.v_head_dim)), "normal"),
                ("wo", (nh * cfg.v_head_dim, h), "normal"),
                *hc, *_mlp_leaves(cfg, li)]
    if cfg.index_topk:
        e, i = cfg.num_experts, cfg.moe_intermediate_size
        ih, idim = cfg.index_heads, cfg.index_head_dim
        return [("n1", (h,), "ones"), ("n2", (h,), "ones"),
                ("wq", (h, q), "normal"), ("wk", (h, kv), "normal"),
                ("wv", (h, kv), "normal"), ("wo", (q, h), "normal"),
                ("qn", (hd,), "ones"), ("kn", (hd,), "ones"),
                ("iq", (h, ih * idim), "normal"), ("ik", (h, idim), "normal"),
                ("ikn", (idim,), "ones"), ("ikb", (idim,), "zeros"),
                ("iw", (h, ih), "normal"), ("router", (h, e), "normal"),
                ("e_gate", (e, h, i), "normal"), ("e_up", (e, h, i), "normal"),
                ("e_down", (e, i, h), "normal")]
    out = [("n1", (h,), "ones"), ("n2", (h,), "ones"), ("n3", (h,), "ones"),
           ("n4", (h,), "ones"), ("wq", (h, q), "normal"),
           ("wk", (h, kv), "normal"), ("wv", (h, kv), "normal"),
           ("wg", (h, q), "normal"), ("wo", (q, h), "normal"),
           ("qn", (hd,), "ones"), ("kn", (hd,), "ones")]
    return out + _mlp_leaves(cfg, li)


def _mlp_leaves(cfg: LmConfig, li: int) -> list[tuple[str, tuple, str]]:
    """A layer's dense SwiGLU, or its router, selection bias, routed
    experts and shared expert (``afmoe`` and ``xing4_0``)."""
    h = cfg.hidden_size
    if li < cfg.num_dense_layers:
        i = cfg.intermediate_size
        return [("w_gate", (h, i), "normal"), ("w_up", (h, i), "normal"),
                ("w_down", (i, h), "normal")]
    e, i = cfg.num_experts, cfg.moe_intermediate_size
    out = [("router", (h, e), "normal"), ("bias", (e,), "bias"),
           ("e_gate", (e, h, i), "normal"), ("e_up", (e, h, i), "normal"),
           ("e_down", (e, i, h), "normal")]
    if cfg.num_shared_experts:
        s = i * cfg.num_shared_experts
        out += [("s_gate", (h, s), "normal"), ("s_up", (h, s), "normal"),
                ("s_down", (s, h), "normal")]
    return out


# our key -> the published name under ``model.layers.<i>.``; matrices are
# stored (out, in) as torch keeps them
HF_NAMES = {
    "n1": "input_layernorm.weight", "n2": "post_attention_layernorm.weight",
    "n3": "pre_mlp_layernorm.weight", "n4": "post_mlp_layernorm.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wg": "self_attn.gate_proj.weight",
    "wo": "self_attn.o_proj.weight", "qn": "self_attn.q_norm.weight",
    "kn": "self_attn.k_norm.weight", "w_gate": "mlp.gate_proj.weight",
    "w_up": "mlp.up_proj.weight", "w_down": "mlp.down_proj.weight",
    "router": "mlp.router.gate.weight", "bias": "mlp.expert_bias",
    "s_gate": "mlp.shared_experts.gate_proj.weight",
    "s_up": "mlp.shared_experts.up_proj.weight",
    "s_down": "mlp.shared_experts.down_proj.weight"}
KEYE_NAMES = {
    "n1": "input_layernorm.weight", "n2": "post_attention_layernorm.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "qn": "self_attn.q_norm.weight", "kn": "self_attn.k_norm.weight",
    "iq": "self_attn.indexer.wq.weight", "ik": "self_attn.indexer.wk.weight",
    "ikn": "self_attn.indexer.k_norm.weight",
    "ikb": "self_attn.indexer.k_norm.bias",
    "iw": "self_attn.indexer.weights_proj.weight",
    "router": "mlp.gate.weight"}
XING_NAMES = {
    "n1": "input_layernorm.weight", "n2": "post_attention_layernorm.weight",
    "wqa": "self_attn.q_a_proj.weight",
    "qan": "self_attn.q_a_layernorm.weight",
    "wqb": "self_attn.q_b_proj.weight",
    "wkva": "self_attn.kv_a_proj_with_mqa.weight",
    "kvn": "self_attn.kv_a_layernorm.weight",
    "wkvb": "self_attn.kv_b_proj.weight", "wo": "self_attn.o_proj.weight",
    # the hyper-connections: names of this repository's choosing
    "hca_w": "self_attn_hc.proj.weight", "hca_b": "self_attn_hc.bias",
    "hca_a": "self_attn_hc.alpha", "hcm_w": "mlp_hc.proj.weight",
    "hcm_b": "mlp_hc.bias", "hcm_a": "mlp_hc.alpha",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight", "router": "mlp.gate.weight",
    "bias": "mlp.gate.e_score_correction_bias",
    "s_gate": "mlp.shared_experts.gate_proj.weight",
    "s_up": "mlp.shared_experts.up_proj.weight",
    "s_down": "mlp.shared_experts.down_proj.weight"}
QWEN3_NEXT_NAMES = {
    "n1": "input_layernorm.weight", "n2": "post_attention_layernorm.weight",
    "w_qkvz": "linear_attn.in_proj_qkvz.weight",
    "w_ba": "linear_attn.in_proj_ba.weight",
    "conv": "linear_attn.conv1d.weight", "a_log": "linear_attn.A_log",
    "dt_bias": "linear_attn.dt_bias", "norm": "linear_attn.norm.weight",
    "w_out": "linear_attn.out_proj.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "qn": "self_attn.q_norm.weight", "kn": "self_attn.k_norm.weight",
    "router": "mlp.gate.weight",
    "s_gate": "mlp.shared_expert.gate_proj.weight",
    "s_up": "mlp.shared_expert.up_proj.weight",
    "s_down": "mlp.shared_expert.down_proj.weight",
    "sg": "mlp.shared_expert_gate.weight"}
EXPERT_NAMES = {"e_gate": "gate_proj", "e_up": "up_proj",
                "e_down": "down_proj"}


def layer_names(cfg: LmConfig) -> dict:
    """Our key -> the family's published name under its layer."""
    return {"KeyeVL2": KEYE_NAMES, XING: XING_NAMES,
            QWEN3_NEXT: QWEN3_NEXT_NAMES}.get(cfg.model_type, HF_NAMES)


def from_state_dict(cfg: LmConfig, sd: dict) -> dict:
    def get(name):
        try:
            return jnp.asarray(sd[name])
        except KeyError:
            raise LmLoadError(f"weights lack {name!r}") from None

    names = layer_names(cfg)
    layers = []
    for li in range(cfg.num_layers):
        base = f"model.layers.{li}."
        lp = {}
        for name, _shape, kind in layer_leaves(cfg, li):
            if name in EXPERT_NAMES:
                proj = EXPERT_NAMES[name]
                first = cfg.expert_first
                lp[name] = jnp.stack([
                    get(f"{base}mlp.experts.{e}.{proj}.weight").T
                    for e in range(first, first + cfg.num_experts)]
                ).astype(BF16)
            else:
                leaf = get(base + names[name])
                if name == "conv":          # (channels, 1, taps)
                    leaf = leaf.reshape(leaf.shape[0], -1)
                leaf = leaf.T if leaf.ndim == 2 else leaf
                lp[name] = leaf.astype(F32 if kind.startswith("bias")
                                       else BF16)
        layers.append(lp)
    return {"embed": get("model.embed_tokens.weight").astype(BF16),
            "head": get("lm_head.weight").T.astype(BF16),
            "final_norm": get("model.norm.weight").astype(BF16),
            "layers": layers}


def _read_weights(path: Path) -> dict:
    """Every tensor of the directory: the shards an index names, else
    the one ``model.safetensors``."""
    from safetensors.flax import load_file

    index = path / "model.safetensors.index.json"
    if index.exists():
        try:
            shards = sorted(set(json.loads(index.read_text())[
                "weight_map"].values()))
        except (OSError, ValueError, KeyError, AttributeError) as exc:
            raise LmLoadError(f"{index}: no readable weight_map") from exc
    else:
        shards = ["model.safetensors"]
    sd: dict = {}
    for shard in shards:
        if not (path / shard).exists():
            raise LmLoadError(f"{path}: no {shard}")
        sd.update(load_file(str(path / shard)))
    return sd


def load_model_dir(path: str | Path) -> LmAssets:
    path = Path(path)
    try:
        hf = json.loads((path / "config.json").read_text())
    except (OSError, ValueError) as exc:
        raise LmLoadError(f"{path}: no readable config.json") from exc
    tok_file = path / "tokenizer.json"
    if not tok_file.exists():
        # byte ids fed to a 200,192-row vocabulary would still "succeed"
        raise LmLoadError(f"{path}: no tokenizer.json")
    cfg = LmConfig.from_hf(hf)
    params = from_state_dict(cfg, _read_weights(path))
    eos = hf.get("eos_token_id")
    if not (isinstance(eos, int) and 0 <= eos < cfg.vocab_size):
        eos = None
    return LmAssets(cfg=cfg, params=params,
                    tokenizer=_HfTokenizer(tok_file),
                    model_name=hf.get("_name_or_path") or path.name,
                    eos_id=eos)
