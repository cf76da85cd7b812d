"""Operations and bytes that one engine step's ALGORITHM needs for
Xing4.0-29B-A4B (``xing4_0``), from shapes and the step's own record
alone. The yardstick of ``lm_mfu_pct.chapters`` and of the four roofline
shares of ``digest_xing_chapters``: written for the work and not for the
form that does it, each attention counted in the CHEAPER of its two
forms whatever form runs, so that a later change of form reads against
the same counts and none can pass 100%.

One step carries ``prefill`` real tokens of one request from position
``context`` on and one token of each decoding row at ``row_pos``. A
multiply-add is two operations. Per layer:

- projections: ``Wqa``, ``Wqb``, ``Wkva`` and ``Wo``; the dense SwiGLU
  on the first ``first_k_dense_replace`` layers; on the others the
  router, the shared expert and ``num_experts_per_tok`` routed experts;
- ``rows``: a decoding row at position ``p`` in the ABSORBED form: its
  ``p + 1`` latents once for all heads, ``2 * heads * (latent + rank)``
  a key (scores against the whole latent, values its first ``rank``
  lanes), and the absorption and the up-projection of its one query,
  ``2 * heads * nope * rank`` and ``2 * heads * rank * v``. Bytes: each
  latent once, ``Wkvb`` once a call, q in and the heads out. (Expanded,
  a key would cost 8.4 MFLOP; absorbed, 0.07.)
- ``chunk``: a prefill chunk in the EXPANDED form: ONE expansion of its
  context a layer (``2 * rank * heads * (nope + v)`` a position), then
  ``2 * heads * (nope + rope + v)`` a causal pair. Bytes: each latent
  once, ``Wkvb`` once, q in and the heads out; the expanded keys and
  values are temporaries that a fused form never writes. (Absorbed, a
  pair would cost 2 * heads * 1,088: 2.8 times the expanded form from a
  context of a few hundred on.)
- ``hc``: the residual path around each of the layer's two sublayers:
  the projection of the state (``2 * streams * hidden * (2 streams +
  streams^2)``), the pre-mix and the post-mix (``2 * streams * hidden``
  and ``2 * (streams^2 + streams) * hidden``) a token. Bytes: a read of
  ``X``, a read of ``y`` and a write of ``X'`` in float32 (129 KB a token
  at four streams of 3,584), and the projection's weights;
- the head: one row per decoding row, and one for the chunk when it is
  the request's last.

Needed bytes of the whole step are what has to cross HBM at least once:
every weight used (of the experts those that hold a row), embedding
rows, the parts above, the new latents written, the logits out.
"""

from __future__ import annotations

W = 2           # bytes of a bfloat16 weight or latent element
F = 4           # bytes of a float32 activation


def _dims(cfg: dict) -> dict:
    nh = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    return {"h": cfg["hidden_size"], "nh": nh, "nope": nope, "rope": rope,
            "vd": vd, "rank": rank, "latent": rank + rope,
            "qr": cfg["q_lora_rank"], "kvb": rank * nh * (nope + vd),
            "n": cfg["hc_mult"],
            "maps": 2 * cfg["hc_mult"] + cfg["hc_mult"] ** 2}


def per_token_flops(cfg: dict) -> dict:
    d = _dims(cfg)
    h = d["h"]
    return {"projections": 2 * h * d["qr"]
            + 2 * d["qr"] * d["nh"] * (d["nope"] + d["rope"])
            + 2 * h * d["latent"] + 2 * d["nh"] * d["vd"] * h,
            "dense_mlp": 2 * 3 * h * cfg["intermediate_size"],
            "router": 2 * h * cfg["n_routed_experts"],
            "shared": cfg["n_shared_experts"] * 2 * 3 * h
            * cfg["moe_intermediate_size"],
            "experts": cfg["num_experts_per_tok"] * 2 * 3 * h
            * cfg["moe_intermediate_size"],
            "hc": 2 * (2 * d["n"] * h * d["maps"] + 2 * d["n"] * h
                       + 2 * (d["n"] ** 2 + d["n"]) * h),
            "head_row": 2 * h * cfg["vocab_size"]}


def step_cost(cfg: dict, *, prefill: int, context: int, row_pos: list[int],
              last_chunk: bool, experts_held: list[int] | None = None
              ) -> dict:
    """``{"flops", "bytes", "parts": {"experts", "rows", "chunk", "hc"}}``
    of one step; each part ``{"flops", "bytes"}`` summed over the
    layers."""
    layers = cfg["num_hidden_layers"]
    n_dense = min(cfg["first_k_dense_replace"], layers)
    n_moe = layers - n_dense
    d = _dims(cfg)
    h, nh = d["h"], d["nh"]
    i_moe, e, k = (cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                   cfg["num_experts_per_tok"])
    tokens = prefill + len(row_pos)
    head_rows = len(row_pos) + (1 if prefill and last_chunk else 0)
    per = per_token_flops(cfg)
    if experts_held is None:
        experts_held = [min(e, tokens * k)] * n_moe

    row_keys = sum(p + 1 for p in row_pos)
    rows = {"flops": float(layers * (
        row_keys * 2 * nh * (d["latent"] + d["rank"])
        + len(row_pos) * 2 * nh * d["rank"] * (d["nope"] + d["vd"]))),
        "bytes": float(layers * (
            row_keys * d["latent"] * W + (d["kvb"] * W if row_pos else 0)
            + len(row_pos) * nh * (d["latent"] * W + d["rank"] * F)))}
    pairs = prefill * context + prefill * (prefill + 1) // 2
    seen = context + prefill if prefill else 0
    chunk = {"flops": float(layers * (
        pairs * 2 * nh * (d["nope"] + d["rope"] + d["vd"])
        + seen * 2 * d["kvb"])),
        "bytes": float(layers * (
            seen * d["latent"] * W + (d["kvb"] * W if prefill else 0)
            + prefill * nh * ((d["nope"] + d["rope"]) * W + d["vd"] * F)))}
    state = d["n"] * h * F                  # one token's residual state
    hc = {"flops": float(layers * tokens * per["hc"]),
          "bytes": float(layers * 2 * (tokens * (2 * state + h * F)
                                       + d["n"] * h * d["maps"] * W))}
    routed = tokens * k
    experts = {"flops": float(n_moe * tokens * per["experts"]),
               "bytes": float(sum(held * 3 * h * i_moe * W
                                  for held in experts_held)
                              + n_moe * (routed * h * W + routed * h * F))}
    linear = tokens * (layers * per["projections"]
                       + n_dense * per["dense_mlp"]
                       + n_moe * (per["router"] + per["shared"]))
    flops = linear + experts["flops"] + rows["flops"] + chunk["flops"] \
        + hc["flops"] + head_rows * per["head_row"]

    weights = (layers * (h * d["qr"] + d["qr"] * nh * (d["nope"] + d["rope"])
                         + h * d["latent"] + nh * d["vd"] * h)
               + n_dense * 3 * h * cfg["intermediate_size"]
               + n_moe * (h * e + cfg["n_shared_experts"] * 3 * h * i_moe)
               ) * W
    if head_rows:
        weights += h * cfg["vocab_size"] * W
    moved = (tokens * h * W                                 # embedding rows
             + layers * tokens * d["latent"] * W            # latents written
             + head_rows * cfg["vocab_size"] * F)           # logits out
    nbytes = weights + moved + rows["bytes"] + chunk["bytes"] + hc["bytes"] \
        + experts["bytes"]
    return {"flops": float(flops), "bytes": float(nbytes),
            "parts": {"experts": experts, "rows": rows, "chunk": chunk,
                      "hc": hc, "linear_flops": float(linear),
                      "weight_bytes": float(weights)},
            "keys": {"rows": row_keys, "chunk_pairs": pairs,
                     "chunk_context": seen}}


def record_cost(cfg: dict, record: dict) -> dict:
    """:func:`step_cost` of one step record of the engine."""
    load = record.get("expert_load")
    held = [int(x[2]) for x in load] if load and len(load[0]) > 2 else None
    return step_cost(cfg, prefill=record["prefill_tokens"],
                     context=record["context"] or 0,
                     row_pos=record["row_pos"],
                     last_chunk=record["chunk_tag"] in record["emitted"],
                     experts_held=held)


def least_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of operations over peak and bytes over
    bandwidth, and which of the two it is."""
    by_flops = cost["flops"] / peaks["flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes \
        else (by_bytes, "bytes")
