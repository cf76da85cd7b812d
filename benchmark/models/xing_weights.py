"""Seeded weights of Xing4.0-29B-A4B (``xing4_0``), made on the device
leaf by leaf.

The benchmark owns the weights: the driver hands them to the program as
its ``params`` (the nested layout ``vlog_tpu/lm/load.py`` documents, the
recipe copied, not imported), and the plain reference gets the same
values. Matrices N(0, 0.02^2), norm weights 1, the router's selection
bias N(0, 0.01^2) in float32 (large enough to change some choices, as
``afmoe_weights.py``), everything bfloat16 but the float32 leaves below.
One jitted draw per leaf shape: 8.35 GB never cross PCIe and no draw
holds more than one leaf's float32 temporary.

**The hyper-connections' leaves** (``hca_*`` around attention, ``hcm_*``
around the MLP; the configuration's ``assumed``): the projection ``_w``
(streams x hidden, 2 streams + streams^2) N(0, std^2) bfloat16, the bias
``_b`` 0 and the three gates ``_a`` 1, float32. The std is
``HC_LOGIT_STD`` over the root of the projection's inputs (0.48 /
sqrt(14,336) = 0.004 at the published widths) and not the matrices'
0.02: the projection's input is the whole residual state over its root
mean square (14,336 numbers of unit mean square), so its 24 outputs
have a standard deviation of 0.48 so, and of 2.4 at 0.02. At 0.48 the sixteen logits of ``H_res`` make a
matrix whose rows differ by half their size, which twenty Sinkhorn
iterations bring to row and column sums within 1.2e-6 of 1 (1e7 draws,
numpy); at 2.4 some rows are all but one-hot and twenty iterations
leave sums up to 5e-2 off, a property of the draw and not of the
program. (The published initialisation gates the projection by 0.01,
where ``H_res`` is near the identity and Sinkhorn has nothing to do.)

:func:`param_count` is the arithmetic of the cut (ISSUE 35's table).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

INIT_STD = 0.02
BIAS_STD = 0.01
HC_LOGIT_STD = 0.48


def _mlp_leaves(cfg: dict, li: int) -> list[tuple[str, tuple, str]]:
    h = cfg["hidden_size"]
    if li < cfg["first_k_dense_replace"]:
        i = cfg["intermediate_size"]
        return [("w_gate", (h, i), "normal"), ("w_up", (h, i), "normal"),
                ("w_down", (i, h), "normal")]
    e, i = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    s = i * cfg["n_shared_experts"]
    return [("router", (h, e), "normal"), ("bias", (e,), "bias"),
            ("e_gate", (e, h, i), "normal"), ("e_up", (e, h, i), "normal"),
            ("e_down", (e, i, h), "normal"),
            ("s_gate", (h, s), "normal"), ("s_up", (h, s), "normal"),
            ("s_down", (s, h), "normal")]


def layer_leaves(cfg: dict, li: int) -> list[tuple[str, tuple, str]]:
    """``(key, shape, kind)`` of layer ``li``'s leaves; ``kind`` is
    ``normal``, ``hc`` (a hyper-connection's projection), ``bias`` (the
    selection bias), ``ones``, ``f32_zeros`` or ``f32_ones``."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    n = cfg["hc_mult"]
    maps = 2 * n + n * n
    hc = [(f"hc{s}_{part}", shape, kind) for s in "am"
          for part, shape, kind in (("w", (n * h, maps), "hc"),
                                    ("b", (maps,), "f32_zeros"),
                                    ("a", (3,), "f32_ones"))]
    return [("n1", (h,), "ones"), ("n2", (h,), "ones"),
            ("wqa", (h, qr), "normal"), ("qan", (qr,), "ones"),
            ("wqb", (qr, nh * (nope + rope)), "normal"),
            ("wkva", (h, rank + rope), "normal"), ("kvn", (rank,), "ones"),
            ("wkvb", (rank, nh * (nope + vd)), "normal"),
            ("wo", (nh * vd, h), "normal"), *hc, *_mlp_leaves(cfg, li)]


def top_leaves(cfg: dict) -> list[tuple[str, tuple, str]]:
    v, h = cfg["vocab_size"], cfg["hidden_size"]
    return [("embed", (v, h), "normal"), ("head", (h, v), "normal"),
            ("final_norm", (h,), "ones")]


def _size(leaves, only=None) -> int:
    total = 0
    for name, shape, _kind in leaves:
        if only is None or name in only or name[:3] in only:
            k = 1
            for d in shape:
                k *= d
            total += k
    return total


ATTENTION = ("wqa", "qan", "wqb", "wkva", "kvn", "wkvb", "wo")


def param_count(cfg: dict, layers: int | None = None) -> dict:
    """Parameters of ``layers`` layers (default: the configuration's
    ``num_hidden_layers``; the first ``first_k_dense_replace`` of them
    dense) with embedding, head and final norm, and the parts ISSUE 35's
    table names."""
    n = cfg["num_hidden_layers"] if layers is None else layers
    dense = layer_leaves(cfg, 0)
    expert = layer_leaves(cfg, cfg["first_k_dense_replace"])
    n_dense = min(n, cfg["first_k_dense_replace"])
    top = _size(top_leaves(cfg))
    return {"total": n_dense * _size(dense) + (n - n_dense) * _size(expert)
            + top,
            "dense_layer": _size(dense), "expert_layer": _size(expert),
            "attention": _size(dense, ATTENTION),
            "hyper_connections": _size(dense, ("hca", "hcm")),
            "routed_experts": _size(expert, ("e_gate", "e_up", "e_down")),
            "shared_expert": _size(expert, ("s_gate", "s_up", "s_down")),
            "router": _size(expert, ("router", "bias")),
            "embedding_and_head": top - cfg["hidden_size"]}


@partial(jax.jit, static_argnames=("shape", "kind", "init_std"))
def _draw(key, shape, kind, init_std):
    if kind == "bias":
        return jax.random.normal(key, shape, jnp.float32) * BIAS_STD
    if kind in ("normal", "hc"):
        std = init_std if kind == "normal" \
            else HC_LOGIT_STD / shape[0] ** 0.5
        return (jax.random.normal(key, shape, jnp.float32)
                * std).astype(jnp.bfloat16)
    return jnp.full(shape, kind.endswith("ones"),
                    jnp.float32 if kind.startswith("f32") else jnp.bfloat16)


def make_params(cfg: dict, seed: int) -> dict:
    """The whole tree on the default device; the same seed gives the
    same values on the same backend, and the reference is handed the
    very arrays the program served with. ``cfg["init_std"]`` is the
    rehearsal's: at hidden 64 a matrix of N(0, 0.02^2) shrinks its input
    sixfold, every sublayer adds next to nothing to the residual state
    and nothing the residual path does shows in the logits; 0.125 keeps
    a product's size there as 0.02 does at the published 3,584."""
    # the hardware generator, as afmoe_weights.py: threefry took 56 s
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
