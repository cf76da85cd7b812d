"""Shared by the transcript model's tests: the tiny afmoe, KeyeVL2,
xing4_0 and qwen3_next models, their engine and the comparison with the
plain references."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "benchmark") not in sys.path:
    sys.path.insert(0, str(ROOT / "benchmark"))

from reference import afmoe_ref as ref  # noqa: E402
from reference import keye_ref  # noqa: E402
from reference import xing_ref  # noqa: E402

from vlog_tpu.lm.engine import LmEngine  # noqa: E402
from vlog_tpu.lm.load import (EXPERT_NAMES, LmAssets,  # noqa: E402
                              layer_leaves, layer_names)
from vlog_tpu.lm.model import BF16, F32, Geometry, LmConfig  # noqa: E402

INIT_STD = 0.02
BIAS_STD = 0.01
# a hyper-connection's projection at hidden 64: its 24 outputs then have
# a standard deviation of 0.48, as the benchmark's have at hidden 3,584
HC_STD = 0.03

# stated tolerance of the tiny comparison: bfloat16 products at hidden 64
# read up to 0.09 of the logits' spread (measured over the lengths
# below); a mechanism left out reads 0.5 and more
LOGIT_TOL = 0.2
# a router margin under this can fall either way on bfloat16 rounding
ROUTE_EPS = 0.004


class ByteTokenizer:
    """UTF-8 bytes as ids: what ``write_tokenizer``'s file does."""

    def encode(self, text: str) -> list[int]:
        return list(text.encode("utf-8"))

    def decode(self, ids) -> str:
        return bytes(i for i in ids if 0 <= i < 256).decode(
            "utf-8", errors="replace")


def tiny_hf_config(**over) -> dict:
    """A CPU-sized ``afmoe`` config of the published form: 2 dense + 4
    expert layers, one whole period after the dense ones."""
    cfg = {"model_type": "afmoe", "hidden_size": 64, "head_dim": 16,
           "num_attention_heads": 8, "num_key_value_heads": 2,
           "num_hidden_layers": 6, "num_dense_layers": 2,
           "layer_types": [("full_attention" if i % 4 == 3
                            else "sliding_attention") for i in range(6)],
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "num_experts": 8, "num_experts_per_tok": 2,
           "num_shared_experts": 1, "route_norm": True,
           "route_scale": 2.826, "score_func": "sigmoid", "n_group": 1,
           "topk_group": 1, "sliding_window": 16, "vocab_size": 512,
           "rms_norm_eps": 1e-5, "rope_theta": 10000, "mup_enabled": True,
           "tie_word_embeddings": False}
    cfg.update(over)
    return cfg


def random_params(cfg: LmConfig, seed: int, init_std: float = INIT_STD
                  ) -> dict:
    """Seeded weights: matrices N(0, 0.02^2), norm weights 1, the
    selection bias N(0, 0.01^2)."""
    key = jax.random.PRNGKey(int(seed) % (2**31 - 1))
    count = [0]

    def draw(shape, kind):
        count[0] += 1
        k = jax.random.fold_in(key, count[0])
        if kind in ("ones", "zeros"):
            return jnp.full(shape, kind == "ones", BF16)
        if kind in ("bias_ones", "bias_zeros"):
            return jnp.full(shape, kind == "bias_ones", F32)
        if kind == "bias":
            return jax.random.normal(k, shape, F32) * BIAS_STD
        std = HC_STD if kind == "hc" else init_std
        return (jax.random.normal(k, shape, F32) * std).astype(BF16)

    v, h = cfg.vocab_size, cfg.hidden_size
    return {"embed": draw((v, h), "normal"), "head": draw((h, v), "normal"),
            "final_norm": draw((h,), "ones"),
            "layers": [{name: draw(shape, kind)
                        for name, shape, kind in layer_leaves(cfg, li)}
                       for li in range(cfg.num_layers)]}


def to_state_dict(params: dict, names: dict) -> dict:
    """The program's layout under the published names (``names``: the
    family's, ``load.layer_names``), torch layouts."""
    sd = {"model.embed_tokens.weight": params["embed"],
          "lm_head.weight": params["head"].T,
          "model.norm.weight": params["final_norm"]}
    for li, lp in enumerate(params["layers"]):
        base = f"model.layers.{li}."
        for name, leaf in lp.items():
            if name in EXPERT_NAMES:
                for e in range(leaf.shape[0]):
                    sd[f"{base}mlp.experts.{e}.{EXPERT_NAMES[name]}"
                       ".weight"] = leaf[e].T
            else:
                sd[base + names[name]] = leaf.T if leaf.ndim == 2 else leaf
    return sd


def _byte_chars() -> list[str]:
    """The character that stands for each byte in a ByteLevel
    vocabulary (GPT-2's table: printable bytes are themselves, the rest
    follow 255), in byte order."""
    keep = [*range(33, 127), *range(161, 173), *range(174, 256)]
    rest = [b for b in range(256) if b not in keep]
    table = {b: chr(b) for b in keep}
    table.update({b: chr(256 + i) for i, b in enumerate(rest)})
    return [table[b] for b in range(256)]


def write_tokenizer(path: Path) -> None:
    """A ``tokenizer.json`` whose ids are UTF-8 bytes (0..255)."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers

    tok = Tokenizer(models.BPE(
        vocab={ch: b for b, ch in enumerate(_byte_chars())}, merges=[]))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False,
                                                 use_regex=False)
    tok.decoder = decoders.ByteLevel()
    tok.save(str(path))


def save_model_dir(path, hf_config: dict, params: dict, *,
                   shards: int = 1) -> Path:
    """A model directory as ``lm/load.py`` reads it: ``config.json``,
    ``tokenizer.json`` and the weights in one file or, with ``shards``
    over 1, in that many beside an index."""
    from safetensors.flax import save_file

    path = Path(path)
    path.mkdir(parents=True, exist_ok=True)
    (path / "config.json").write_text(json.dumps(hf_config, indent=1))
    write_tokenizer(path / "tokenizer.json")
    sd = to_state_dict(params, layer_names(LmConfig.from_hf(hf_config)))
    if shards == 1:
        save_file(sd, str(path / "model.safetensors"))
        return path
    names = sorted(sd)
    weight_map = {}
    for i in range(shards):
        file = f"model-{i + 1:05d}-of-{shards:05d}.safetensors"
        part = {n: sd[n] for n in names[i::shards]}
        save_file(part, str(path / file))
        weight_map.update(dict.fromkeys(part, file))
    (path / "model.safetensors.index.json").write_text(
        json.dumps({"weight_map": weight_map}))
    return path


def tiny(seed=7, bias_scale=20.0, router_scale=10.0, **over):
    hf = tiny_hf_config(**over)
    cfg = LmConfig.from_hf(hf)
    params = random_params(cfg, seed)
    for lp in params["layers"]:
        if "bias" in lp:            # large enough to change choices
            lp["bias"] = lp["bias"] * bias_scale
            # scores spread over (0, 1), so that the chosen's sum (what
            # route_norm divides by) differs from token to token
            lp["router"] = lp["router"] * router_scale
    return hf, cfg, params


def geometry(cfg, rows=4, chunk=8, page=4, cap=128, block=2,
             full_pages=None):
    base = Geometry(rows=rows, chunk=chunk, page=page, context_cap=cap)
    ring = base.ring(cfg.sliding_window)
    return Geometry(rows=rows, chunk=chunk, page=page, context_cap=cap,
                    kv_block_pages=block,
                    window_pages=rows * ring + 1 if ring else 0,
                    full_pages=full_pages or rows * base.max_pages + 1)


def engine(cfg, params, **geo):
    eng = LmEngine(LmAssets(cfg, params, None, "tiny"),
                   geometry=geometry(cfg, **geo))
    eng.prepare(300)
    return eng


def compare(req, hf, params, off=()):
    """Largest logit error (over the reference's spread) of a finished
    request's captured steps whose router margins stand, and the rank
    gaps of its tokens."""
    toks = req.tokens
    full = np.concatenate([req.prompt, toks[:-1]]).astype(np.int32)
    steps = sorted(req.logits)
    out = ref.forward(params, hf, full,
                      [req.prompt.size - 1 + i for i in steps], off=off)
    errs, gaps = [], []
    for row, i in enumerate(steps):
        if out["route_gap"][row] < ROUTE_EPS:
            continue
        errs.append(ref.logit_error(req.logits[i], out["logits"][row]))
        gaps.append(ref.rank_gap(toks[i], out["logits"][row]))
    return errs, gaps


# ---- KeyeVL2 at tiny widths ---------------------------------------------

# a selection margin (a query's 16th index score over its 17th) under
# this can fall either way on the bfloat16 rounding of the indexer's
# operands; the other side then attends another key
SELECT_EPS = 0.05


def tiny_keye_hf_config(**over) -> dict:
    """A CPU-sized config of the published ``KeyeVL2`` form: four layers
    of sparse attention (4 index heads of 8, 16 keys kept) and 8 experts
    top 2."""
    cfg = {"model_type": "KeyeVL2", "hidden_size": 64, "head_dim": 16,
           "num_attention_heads": 8, "num_key_value_heads": 2,
           "num_hidden_layers": 4, "intermediate_size": 96,
           "moe_intermediate_size": 32, "num_experts": 8,
           "num_local_experts": 8, "num_experts_per_tok": 2,
           "norm_topk_prob": True, "decoder_sparse_step": 1,
           "mlp_only_layers": [], "attention_bias": False,
           "sliding_window": None, "use_sliding_window": False,
           "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
           "rope_scaling": {"mrope_section": [2, 3, 3],
                            "rope_type": "default", "type": "default"},
           "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                         "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                         "q_chunk_size": 8, "topk": 16},
           "tie_word_embeddings": False}
    cfg.update(over)
    return cfg


def tiny_keye(seed=9, index_scale=40.0, router_scale=30.0, **over):
    hf = tiny_keye_hf_config(**over)
    cfg = LmConfig.from_hf(hf)
    params = random_params(cfg, seed)
    for lp in params["layers"]:
        # index scores and router logits spread far enough that most
        # choices stand by more than bfloat16's rounding
        for name in ("iq", "iw"):
            lp[name] = lp[name] * index_scale
        lp["router"] = lp["router"] * router_scale
    return hf, cfg, params


def compare_keye(req, hf, params, **how):
    """As :func:`compare`, against ``keye_ref``: positions whose router
    or selection margin stands."""
    toks = req.tokens
    full = np.concatenate([req.prompt, toks[:-1]]).astype(np.int32)
    steps = sorted(req.logits)
    out = keye_ref.forward(params, hf, full,
                           [req.prompt.size - 1 + i for i in steps], **how)
    errs, gaps = [], []
    for row, i in enumerate(steps):
        if out["route_gap"][row] < ROUTE_EPS \
                or out["select_gap"][row] < SELECT_EPS:
            continue
        errs.append(keye_ref.logit_error(req.logits[i], out["logits"][row]))
        gaps.append(keye_ref.rank_gap(toks[i], out["logits"][row]))
    return errs, gaps


# ---- xing4_0 at tiny widths ---------------------------------------------

def tiny_xing_hf_config(**over) -> dict:
    """A CPU-sized config of the published ``xing4_0`` form: latent
    attention (4 heads of 16 + 8 over a latent of 16 + 8), four residual
    streams, 1 dense + 3 expert layers of 8 experts top 2 beside a shared
    one. YaRN's original length is 32 so that the test's positions
    cross its ramp."""
    cfg = {"model_type": "xing4_0", "hidden_size": 64,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 8, "v_head_dim": 16, "num_hidden_layers": 4,
           "first_k_dense_replace": 1, "intermediate_size": 96,
           "moe_intermediate_size": 32, "n_routed_experts": 8,
           "num_experts_per_tok": 2, "n_shared_experts": 1,
           "norm_topk_prob": True, "routed_scaling_factor": 2,
           "scoring_func": "sigmoid", "topk_method": "noaux_tc",
           "n_group": 1, "topk_group": 1, "moe_layer_freq": 1, "ep_size": 1,
           "hidden_act": "silu", "vocab_size": 512, "rms_norm_eps": 1e-6,
           "rope_theta": 10000, "max_position_embeddings": 2048,
           "rope_scaling": {"type": "yarn", "factor": 64,
                            "original_max_position_embeddings": 32,
                            "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                            "mscale_all_dim": 1},
           "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
           "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
           "attention_bias": False, "tie_word_embeddings": False,
           "num_nextn_predict_layers": 1}
    cfg.update(over)
    return cfg


def tiny_xing(seed=11, bias_scale=20.0, init_std=0.125, **over):
    """Matrices N(0, 0.125^2): at hidden 64 a product then keeps its
    input's size, as N(0, 0.02^2) does at the published 3,584; at 0.02 a
    sublayer would add next to nothing to the residual state and what
    the residual path does would not show in the logits."""
    hf = tiny_xing_hf_config(**over)
    cfg = LmConfig.from_hf(hf)
    params = random_params(cfg, seed, init_std)
    for lp in params["layers"]:
        if "bias" in lp:            # large enough to change choices
            lp["bias"] = lp["bias"] * bias_scale
    return hf, cfg, params


def xing_rows(req, hf, params, **how):
    """The reference's full forward pass over a finished request's
    prompt plus served tokens, at its captured steps."""
    full = np.concatenate([req.prompt, req.tokens[:-1]]).astype(np.int32)
    steps = sorted(req.logits)
    return steps, xing_ref.forward(
        params, hf, full, [req.prompt.size - 1 + i for i in steps], **how)


# ---- qwen3_next at tiny widths --------------------------------------------

def tiny_qwen_hf_config(**over) -> dict:
    """A CPU-sized config of the published ``qwen3_next`` form: three
    Gated DeltaNet layers (2 key heads of 16 repeated to 4 value heads of
    16) then gated attention (4 heads of 32 over 2 K/V heads, rotary on
    8 of 32 dims); 8 experts held of a router's 16, top 3, beside a gated
    shared expert."""
    cfg = {"model_type": "qwen3_next", "hidden_size": 64, "head_dim": 32,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "num_hidden_layers": 4, "full_attention_interval": 4,
           "linear_num_key_heads": 2, "linear_num_value_heads": 4,
           "linear_key_head_dim": 16, "linear_value_head_dim": 16,
           "linear_conv_kernel_dim": 4, "partial_rotary_factor": 0.25,
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "shared_expert_intermediate_size": 32, "num_experts": 8,
           "published_num_experts": 16, "first_held_expert": 0,
           "num_experts_per_tok": 3, "norm_topk_prob": True,
           "decoder_sparse_step": 1, "mlp_only_layers": [],
           "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 1e7,
           "hidden_act": "silu", "tie_word_embeddings": False,
           "use_sliding_window": False, "rope_scaling": None,
           "max_position_embeddings": 2048}
    cfg.update(over)
    return cfg


def tiny_qwen(seed=13, init_std=0.125, **over):
    """Matrices N(0, 0.125^2) (``tiny_xing`` says why); ``A_log`` spread
    so that the value heads' decays run from a few positions' memory to
    a few hundred, as the benchmark's draw does at its widths."""
    hf = tiny_qwen_hf_config(**over)
    cfg = LmConfig.from_hf(hf)
    params = random_params(cfg, seed, init_std)
    nv = cfg.linear_value_heads
    for lp in params["layers"]:
        if "a_log" in lp:
            lp["a_log"] = jnp.log(jnp.geomspace(1e-2, 1.0, nv)).astype(F32)
            lp["dt_bias"] = jnp.zeros((nv,), F32)
    return hf, cfg, params


def qwen_rows(req, hf, params, **how):
    """The reference's full forward pass over a finished request's
    prompt plus served tokens, at its captured steps."""
    from reference import qwen3next_ref

    full = np.concatenate([req.prompt, req.tokens[:-1]]).astype(np.int32)
    steps = sorted(req.logits)
    return steps, qwen3next_ref.forward(
        params, hf, full, [req.prompt.size - 1 + i for i in steps], **how)
