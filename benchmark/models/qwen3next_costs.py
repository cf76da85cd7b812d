"""Operations and bytes that one engine step's ALGORITHM needs for
Qwen3-Next-80B-A3B (``qwen3_next``), from shapes and the step's own
record alone. The yardstick of ``lm_mfu_pct.talks`` and of the four
roofline shares of ``digest_qwen3next_talks``: written for the work and
not for the form that does it, so that a later form (a kernel in place
of XLA) reads against the same counts and none can pass 100%.

One step carries ``prefill`` real tokens of one request from position
``context`` on and one token of each decoding row at ``row_pos``. A
multiply-add is two operations; dk, dv the DeltaNet's head dims, ``nv``
its value heads, ``C = SUB`` its sub-chunk. Per layer:

- projections: a DeltaNet layer's ``Wqkvz``, ``Wba``, ``Wout`` and its
  conv (4 taps over 8,192 channels); the attention layer's ``Wq`` (with
  the gate), ``Wk``, ``Wv``, ``Wo``; every layer's router (all 512
  outputs), shared expert and its gate;
- ``gdn_chunk``: a prefill chunk through the CHUNKWISE gated delta rule,
  a value head and a sub-chunk of C positions: the strictly lower
  ``(beta k) k^T`` (C^2 dk / 2 multiply-adds), its unit-triangular
  inverse (C^3 / 6), ``T (beta v)`` and ``T (beta k e^G)`` (C^2 (dv + dk)
  / 2), the causal ``q k^T`` and its product with the new values (C^2
  (dk + dv) / 2), and three products with the state (``W S``, ``q S``,
  ``k^T V``: 3 C dk dv); per token ``C (3 dk + 2 dv) + C^2 / 3 + 6 dk
  dv`` operations a value head. Bytes: the state read and written once a
  sequence a chunk (float32), the chunk's q, k (a key head's, not
  repeated), v, g and beta in and o out, float32;
- ``gdn_rows``: a decoding row, one step of the recurrence a value head:
  the decay, ``S^T k``, the rank-one update and ``S^T q`` (7 dk dv
  operations); bytes: its state read and written once (float32), q, k,
  v, g, beta in, o out;
- ``attn``: the gated full layer's attention (scope ``lm.attn.full``): a
  decoding row at position ``p`` over its ``p + 1`` keys, a chunk over
  its causal pairs, ``2 * heads * head_dim * 2`` a key (scores and
  values) and the gate; bytes: each key's K and V once a sequence (2
  heads of 256, bfloat16), q in and the heads out;
- ``experts``: the pairs routed to the experts held here
  (``held_choices`` of the record, counted on the device), ``2 * 3 *
  hidden * width`` each; bytes: the held experts that hold any row
  (``expert_load``), the routed rows in (bfloat16) and out (float32);
- the head: one row per decoding row, and one for the chunk when it is
  the request's last.

Needed bytes of the whole step are what has to cross HBM at least once:
every weight used, embedding rows, the parts above, the new K/V written,
the logits out.
"""

from __future__ import annotations

W = 2           # bytes of a bfloat16 weight or K/V element
F = 4           # bytes of a float32 activation or state element
SUB = 64        # the chunkwise form's sub-chunk (model.py GDN_SUB)


def _dims(cfg: dict) -> dict:
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    every = int(cfg.get("full_attention_interval", 4))
    layers = cfg["num_hidden_layers"]
    n_full = layers // every
    return {"h": cfg["hidden_size"], "nk": nk, "nv": nv, "dk": dk, "dv": dv,
            "conv": 2 * nk * dk + nv * dv, "taps": cfg["linear_conv_kernel_dim"],
            "qkvz": 2 * nk * dk + 2 * nv * dv, "nh": nh, "nkv": nkv,
            "hd": hd, "n_full": n_full, "n_lin": layers - n_full,
            "router": cfg.get("published_num_experts", cfg["num_experts"])}


def per_token_flops(cfg: dict) -> dict:
    d = _dims(cfg)
    h, dk, dv = d["h"], d["dk"], d["dv"]
    return {"gdn_projections": 2 * h * (d["qkvz"] + 2 * d["nv"])
            + 2 * d["nv"] * dv * h + 2 * d["taps"] * d["conv"],
            "attn_projections": 2 * h * (2 * d["nh"] * d["hd"]
                                         + 2 * d["nkv"] * d["hd"])
            + 2 * d["nh"] * d["hd"] * h,
            "router": 2 * h * d["router"],
            "shared": 2 * 3 * h * cfg["shared_expert_intermediate_size"]
            + 2 * h,
            "expert_pair": 2 * 3 * h * cfg["moe_intermediate_size"],
            "gdn_chunk": d["nv"] * (SUB * (3 * dk + 2 * dv) + SUB * SUB / 3
                                    + 6 * dk * dv),
            "gdn_row": d["nv"] * 7 * dk * dv,
            "attn_key": 2 * d["nh"] * d["hd"] * 2,
            "head_row": 2 * h * cfg["vocab_size"]}


def step_cost(cfg: dict, *, prefill: int, context: int, row_pos: list[int],
              last_chunk: bool, held_pairs: int | None = None,
              experts_busy: list[int] | None = None) -> dict:
    """``{"flops", "bytes", "parts": {"gdn_chunk", "gdn_rows", "attn",
    "experts"}}`` of one step; each part ``{"flops", "bytes"}`` summed
    over the layers. ``held_pairs``: valid pairs on the held experts over
    all layers (default: the held share of all); ``experts_busy``: per
    layer the held experts that hold any row (default: all that could)."""
    d = _dims(cfg)
    h, nv, dk, dv = d["h"], d["nv"], d["dk"], d["dv"]
    layers = d["n_lin"] + d["n_full"]
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    i_moe = cfg["moe_intermediate_size"]
    rows = len(row_pos)
    tokens = prefill + rows
    head_rows = rows + (1 if prefill and last_chunk else 0)
    per = per_token_flops(cfg)
    if held_pairs is None:
        held_pairs = tokens * k * layers * e // d["router"]
    if experts_busy is None:
        experts_busy = [min(e, tokens * k)] * layers

    state = nv * dk * dv * F
    # q and k a key head, v, g, beta in; o out; float32
    token_io = (2 * d["nk"] * dk + nv * dv + 2 * nv + nv * dv) * F
    gdn_chunk = {"flops": float(d["n_lin"] * prefill * per["gdn_chunk"]),
                 "bytes": float(d["n_lin"] * ((2 * state if prefill else 0)
                                              + prefill * token_io))}
    gdn_rows = {"flops": float(d["n_lin"] * rows * per["gdn_row"]),
                "bytes": float(d["n_lin"] * rows * (2 * state + token_io))}

    row_keys = sum(p + 1 for p in row_pos)
    pairs = prefill * context + prefill * (prefill + 1) // 2
    seen = context + prefill if prefill else 0
    kv_b = 2 * d["nkv"] * d["hd"] * W                   # a key's K and V
    q_out = d["nh"] * d["hd"] * (W + F + F)             # q, gate, heads out
    attn = {"flops": float(d["n_full"] * ((row_keys + pairs) * per["attn_key"]
                                          + tokens * d["nh"] * d["hd"])),
            "bytes": float(d["n_full"] * ((row_keys + seen) * kv_b
                                          + tokens * q_out))}
    routed = held_pairs
    experts = {"flops": float(held_pairs * per["expert_pair"]),
               "bytes": float(sum(experts_busy) * 3 * h * i_moe * W
                              + routed * h * (W + F))}
    linear = tokens * (d["n_lin"] * per["gdn_projections"]
                       + d["n_full"] * per["attn_projections"]
                       + layers * (per["router"] + per["shared"]))
    flops = linear + gdn_chunk["flops"] + gdn_rows["flops"] + attn["flops"] \
        + experts["flops"] + head_rows * per["head_row"]

    s = cfg["shared_expert_intermediate_size"]
    weights = (d["n_lin"] * (h * (d["qkvz"] + 2 * nv) + nv * dv * h
                             + d["taps"] * d["conv"])
               + d["n_full"] * (h * (2 * d["nh"] * d["hd"]
                                     + 2 * d["nkv"] * d["hd"])
                                + d["nh"] * d["hd"] * h)
               + layers * (h * d["router"] + 3 * h * s + h)) * W
    if head_rows:
        weights += h * cfg["vocab_size"] * W
    moved = (tokens * h * W                                 # embedding rows
             + d["n_full"] * tokens * kv_b                  # K/V written
             + head_rows * cfg["vocab_size"] * F)           # logits out
    nbytes = weights + moved + gdn_chunk["bytes"] + gdn_rows["bytes"] \
        + attn["bytes"] + experts["bytes"]
    return {"flops": float(flops), "bytes": float(nbytes),
            "parts": {"gdn_chunk": gdn_chunk, "gdn_rows": gdn_rows,
                      "attn": attn, "experts": experts,
                      "linear_flops": float(linear),
                      "weight_bytes": float(weights)},
            "keys": {"rows": row_keys, "chunk_pairs": pairs,
                     "chunk_context": seen}}


def record_cost(cfg: dict, record: dict) -> dict:
    """:func:`step_cost` of one step record of the engine."""
    load = record.get("expert_load")
    busy = [int(x[2]) for x in load] if load and len(load[0]) > 2 else None
    held = record.get("held_choices")
    return step_cost(cfg, prefill=record["prefill_tokens"],
                     context=record["context"] or 0,
                     row_pos=record["row_pos"],
                     last_chunk=record["chunk_tag"] in record["emitted"],
                     held_pairs=int(held[0]) if held else None,
                     experts_busy=busy)


def least_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of operations over peak and bytes over
    bandwidth, and which of the two it is."""
    by_flops = cost["flops"] / peaks["flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes \
        else (by_bytes, "bytes")
