"""Seeded weights of Qwen3-Next-80B-A3B (``qwen3_next``), made on the
device leaf by leaf.

The benchmark owns the weights: the driver hands them to the program as
its ``params`` (the nested layout ``vlog_tpu/lm/load.py`` documents, the
recipe copied, not imported), and the plain reference gets the same
values. Matrices N(0, 0.02^2) bfloat16; the zero-centred norms' weights
0 (their factor ``1 + w`` is 1) and the DeltaNet's gated norm's 1; the
conv's weight N(0, ``CONV_STD``^2); ``A_log`` and ``dt_bias`` float32
(the configuration's ``assumed``): ``A`` log-uniform over ``A_RANGE`` a
value head and ``dt_bias`` 0, so that the heads' decays run from a
token's memory to thousands (the published initialisation forgets
everything within a few tokens, and nothing the state carries would
show). One jitted draw per leaf shape: 7.98 GB never cross PCIe.

:func:`param_count` is the arithmetic of the cut (the configuration's
``cut``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

INIT_STD = 0.02
CONV_STD = 0.25
A_RANGE = (1e-3, 1.0)


def layer_kind(cfg: dict, li: int) -> str:
    every = int(cfg.get("full_attention_interval", 4))
    return "full_attention" if (li + 1) % every == 0 else "linear_attention"


def layer_leaves(cfg: dict, li: int) -> list[tuple[str, tuple, str]]:
    """``(key, shape, kind)`` of layer ``li``'s leaves; ``kind`` is
    ``normal``, ``conv``, ``zeros``, ``ones``, ``a_log`` or
    ``f32_zeros``."""
    h = cfg["hidden_size"]
    norms = [("n1", (h,), "zeros"), ("n2", (h,), "zeros")]
    if layer_kind(cfg, li) == "linear_attention":
        nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
        dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
        conv = 2 * nk * dk + nv * dv
        mixer = [("w_qkvz", (h, 2 * nk * dk + 2 * nv * dv), "normal"),
                 ("w_ba", (h, 2 * nv), "normal"),
                 ("conv", (cfg["linear_conv_kernel_dim"], conv), "conv"),
                 ("a_log", (nv,), "a_log"), ("dt_bias", (nv,), "f32_zeros"),
                 ("norm", (dv,), "ones"), ("w_out", (nv * dv, h), "normal")]
    else:
        nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                       cfg["head_dim"])
        mixer = [("wq", (h, 2 * nh * hd), "normal"),
                 ("wk", (h, nkv * hd), "normal"),
                 ("wv", (h, nkv * hd), "normal"),
                 ("wo", (nh * hd, h), "normal"),
                 ("qn", (hd,), "zeros"), ("kn", (hd,), "zeros")]
    e, i = cfg["num_experts"], cfg["moe_intermediate_size"]
    s = cfg["shared_expert_intermediate_size"]
    router = cfg.get("published_num_experts", e)
    return norms + mixer + [
        ("router", (h, router), "normal"),
        ("e_gate", (e, h, i), "normal"), ("e_up", (e, h, i), "normal"),
        ("e_down", (e, i, h), "normal"), ("s_gate", (h, s), "normal"),
        ("s_up", (h, s), "normal"), ("s_down", (s, h), "normal"),
        ("sg", (h, 1), "normal")]


def top_leaves(cfg: dict) -> list[tuple[str, tuple, str]]:
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return [("embed", (v, h), "normal"), ("head", (h, v), "normal"),
            ("final_norm", (h,), "zeros")]


def _size(leaves, only=None) -> int:
    total = 0
    for name, shape, _kind in leaves:
        if only is None or name in only:
            k = 1
            for d in shape:
                k *= d
            total += k
    return total


DELTANET = ("w_qkvz", "w_ba", "conv", "a_log", "dt_bias", "norm", "w_out")
ATTENTION = ("wq", "wk", "wv", "wo", "qn", "kn")
OUTSIDE_EXPERTS = ("router", "s_gate", "s_up", "s_down", "sg", "n1", "n2")
ROUTED = ("e_gate", "e_up", "e_down")


def param_count(cfg: dict, layers: int | None = None,
                experts: int | None = None) -> dict:
    """Parameters of ``layers`` layers (default: the configuration's) with
    ``experts`` routed experts a layer (default: those held here) and
    embedding, head and final norm, and the parts the configuration's
    ``cut`` names."""
    n = cfg["num_hidden_layers"] if layers is None else layers
    c = cfg if experts is None else {**cfg, "num_experts": experts}
    every = int(cfg.get("full_attention_interval", 4))
    lin, full = layer_leaves(c, 0), layer_leaves(c, every - 1)
    n_full = n // every
    top = _size(top_leaves(c))
    return {"total": (n - n_full) * _size(lin) + n_full * _size(full) + top,
            "deltanet_layer": _size(lin), "attention_layer": _size(full),
            "deltanet": _size(lin, DELTANET),
            "attention": _size(full, ATTENTION),
            "outside_experts": _size(lin, OUTSIDE_EXPERTS),
            "routed_expert": _size(lin, ROUTED) // c["num_experts"],
            "embedding_and_head": top - cfg["hidden_size"]}


@partial(jax.jit, static_argnames=("shape", "kind", "init_std"))
def _draw(key, shape, kind, init_std):
    if kind == "a_log":
        lo, hi = A_RANGE
        u = jax.random.uniform(key, shape, jnp.float32)
        return jnp.log(lo) + u * (jnp.log(hi) - jnp.log(lo))
    if kind in ("normal", "conv"):
        std = init_std if kind == "normal" else CONV_STD
        return (jax.random.normal(key, shape, jnp.float32)
                * std).astype(jnp.bfloat16)
    return jnp.full(shape, kind.endswith("ones"),
                    jnp.float32 if kind.startswith("f32") else jnp.bfloat16)


def make_params(cfg: dict, seed: int) -> dict:
    """The whole tree on the default device; the same seed gives the
    same values on the same backend, and the reference is handed the
    very arrays the program served with. ``cfg["init_std"]`` is the
    rehearsal's (``xing_weights.py`` says why)."""
    key = jax.random.key(int(seed) % (2**31 - 1), impl="rbg")
    init_std = float(cfg.get("init_std", INIT_STD))
    n = [0]

    def leaf(shape, kind):
        n[0] += 1
        return _draw(jax.random.fold_in(key, n[0]), shape, kind, init_std)

    out = {name: leaf(shape, kind) for name, shape, kind in top_leaves(cfg)}
    out["layers"] = [{name: leaf(shape, kind)
                      for name, shape, kind in layer_leaves(cfg, li)}
                     for li in range(cfg["num_hidden_layers"])]
    return out
