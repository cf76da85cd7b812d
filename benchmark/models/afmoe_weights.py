"""Seeded ``afmoe`` weights, made on the device leaf by leaf.

The benchmark owns the weights: the driver hands them to the program as
its ``params`` (the nested layout ``vlog_tpu/lm/load.py`` documents, the
recipe copied, not imported), and the plain reference gets the same
values. Matrices N(0, 0.02^2), norm weights 1, the router's selection
bias N(0, 0.01^2) in float32 (large enough to change some choices);
everything else bfloat16. One jitted draw per leaf shape: 8.6 GB never
cross PCIe and no draw holds more than one leaf's float32 temporary.

:func:`param_count` is the arithmetic of the cut (ISSUE 29's table).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

INIT_STD = 0.02
BIAS_STD = 0.01
SLIDING = "sliding_attention"


def layer_leaves(cfg: dict, li: int) -> list[tuple[str, tuple, str]]:
    """``(key, shape, kind)`` of layer ``li``'s leaves; ``kind`` is
    ``normal``, ``ones`` or ``bias``."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    out = [("n1", (h,), "ones"), ("n2", (h,), "ones"), ("n3", (h,), "ones"),
           ("n4", (h,), "ones"), ("wq", (h, q), "normal"),
           ("wk", (h, kv), "normal"), ("wv", (h, kv), "normal"),
           ("wg", (h, q), "normal"), ("wo", (q, h), "normal"),
           ("qn", (hd,), "ones"), ("kn", (hd,), "ones")]
    if li < cfg["num_dense_layers"]:
        i = cfg["intermediate_size"]
        out += [("w_gate", (h, i), "normal"), ("w_up", (h, i), "normal"),
                ("w_down", (i, h), "normal")]
    else:
        e, i = cfg["num_experts"], cfg["moe_intermediate_size"]
        out += [("router", (h, e), "normal"), ("bias", (e,), "bias"),
                ("e_gate", (e, h, i), "normal"), ("e_up", (e, h, i), "normal"),
                ("e_down", (e, i, h), "normal")]
        s = i * cfg["num_shared_experts"]
        if s:
            out += [("s_gate", (h, s), "normal"), ("s_up", (h, s), "normal"),
                    ("s_down", (s, h), "normal")]
    return out


def top_leaves(cfg: dict) -> list[tuple[str, tuple, str]]:
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return [("embed", (v, h), "normal"), ("head", (h, v), "normal"),
            ("final_norm", (h,), "ones")]


def param_count(cfg: dict, layers: int | None = None) -> dict:
    """Parameters of the first ``layers`` layers (default: the
    configuration's ``num_hidden_layers``) with embedding and head, and
    the parts the ISSUE's table names. The selection bias (128 a layer)
    is counted: it is held and read."""
    n = cfg["num_hidden_layers"] if layers is None else layers

    def size(leaves):
        total = 0
        for _name, shape, _kind in leaves:
            k = 1
            for d in shape:
                k *= d
            total += k
        return total

    per_layer = [size(layer_leaves(cfg, li)) for li in range(n)]
    attn = size([leaf for leaf in layer_leaves(cfg, 0)
                 if leaf[0] in ("wq", "wk", "wv", "wg", "wo", "qn", "kn")])
    top = size(top_leaves(cfg))
    return {"total": sum(per_layer) + top, "attention": attn,
            "dense_layer": per_layer[0] if n else 0,
            "expert_layer": per_layer[-1] if n > cfg["num_dense_layers"]
            else 0,
            "embedding_and_head": top - cfg["hidden_size"]}


@partial(jax.jit, static_argnames=("shape", "kind"))
def _draw(key, shape, kind):
    if kind == "ones":
        return jnp.ones(shape, jnp.bfloat16)
    if kind == "bias":
        return jax.random.normal(key, shape, jnp.float32) * BIAS_STD
    return (jax.random.normal(key, shape, jnp.float32)
            * INIT_STD).astype(jnp.bfloat16)


def make_params(cfg: dict, seed: int) -> dict:
    """The whole tree on the default device; the same seed gives the
    same values on the same backend, and the reference is handed the
    very arrays the program served with."""
    # the hardware generator: 4.3 G normals from threefry took 56 s of
    # set-up on the chip (my chip run, PR 29)
    key = jax.random.key(int(seed) % (2**31 - 1), impl="rbg")
    n = [0]

    def leaf(shape, kind):
        n[0] += 1
        return _draw(jax.random.fold_in(key, n[0]), shape, kind)

    out = {name: leaf(shape, kind) for name, shape, kind in top_leaves(cfg)}
    out["layers"] = [{name: leaf(shape, kind)
                      for name, shape, kind in layer_leaves(cfg, li)}
                     for li in range(cfg["num_hidden_layers"])]
    return out
