"""Seeded weights of Keye-VL-2.0's language model (``KeyeVL2``), made on
the device leaf by leaf.

The benchmark owns the weights: the driver hands them to the program as
its ``params`` (the nested layout ``vlog_tpu/lm/load.py`` documents, the
recipe copied, not imported), and the plain reference gets the same
values. Matrices N(0, 0.02^2) (the indexer's three projections too), norm
weights 1, the indexer key's LayerNorm bias 0; everything bfloat16. One
jitted draw per leaf shape: 8.75 GB never cross PCIe and no draw holds
more than one leaf's float32 temporary.

:func:`param_count` is the arithmetic of the cut (ISSUE 33's table).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def layer_leaves(cfg: dict) -> list[tuple[str, tuple, str]]:
    """``(key, shape, kind)`` of a layer's leaves (every layer is the
    same); ``kind`` is ``normal``, ``ones`` or ``zeros``."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    sa = cfg["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    e, i = cfg["num_experts"], cfg["moe_intermediate_size"]
    return [("n1", (h,), "ones"), ("n2", (h,), "ones"),
            ("wq", (h, q), "normal"), ("wk", (h, kv), "normal"),
            ("wv", (h, kv), "normal"), ("wo", (q, h), "normal"),
            ("qn", (hd,), "ones"), ("kn", (hd,), "ones"),
            ("iq", (h, ih * idim), "normal"), ("ik", (h, idim), "normal"),
            ("ikn", (idim,), "ones"), ("ikb", (idim,), "zeros"),
            ("iw", (h, ih), "normal"), ("router", (h, e), "normal"),
            ("e_gate", (e, h, i), "normal"), ("e_up", (e, h, i), "normal"),
            ("e_down", (e, i, h), "normal")]


def top_leaves(cfg: dict) -> list[tuple[str, tuple, str]]:
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return [("embed", (v, h), "normal"), ("head", (h, v), "normal"),
            ("final_norm", (h,), "ones")]


def _size(leaves, only=None) -> int:
    total = 0
    for name, shape, _kind in leaves:
        if only is None or name in only:
            k = 1
            for d in shape:
                k *= d
            total += k
    return total


def param_count(cfg: dict, layers: int | None = None) -> dict:
    """Parameters of ``layers`` layers (default: the configuration's
    ``num_hidden_layers``) with embedding, head and final norm, and the
    parts ISSUE 33's table names."""
    n = cfg["num_hidden_layers"] if layers is None else layers
    leaves = layer_leaves(cfg)
    top = _size(top_leaves(cfg))
    return {"total": n * _size(leaves) + top, "layer": _size(leaves),
            "experts": _size(leaves, ("e_gate", "e_up", "e_down")),
            "attention": _size(leaves, ("wq", "wk", "wv", "wo", "qn", "kn")),
            "indexer": _size(leaves, ("iq", "ik", "ikn", "ikb", "iw")),
            "router": _size(leaves, ("router",)),
            "norms": _size(leaves, ("n1", "n2")),
            "embedding_and_head": top - cfg["hidden_size"]}


@partial(jax.jit, static_argnames=("shape", "kind"))
def _draw(key, shape, kind):
    if kind != "normal":
        return jnp.full(shape, kind == "ones", jnp.bfloat16)
    return (jax.random.normal(key, shape, jnp.float32)
            * INIT_STD).astype(jnp.bfloat16)


def make_params(cfg: dict, seed: int) -> dict:
    """The whole tree on the default device; the same seed gives the
    same values on the same backend, and the reference is handed the
    very arrays the program served with."""
    # the hardware generator, as afmoe_weights.py: threefry took 56 s
    key = jax.random.key(int(seed) % (2**31 - 1), impl="rbg")
    n = [0]

    def leaf(shape, kind):
        n[0] += 1
        return _draw(jax.random.fold_in(key, n[0]), shape, kind)

    out = {name: leaf(shape, kind) for name, shape, kind in top_leaves(cfg)}
    out["layers"] = [{name: leaf(shape, kind)
                      for name, shape, kind in layer_leaves(cfg)}
                     for _ in range(cfg["num_hidden_layers"])]
    return out
