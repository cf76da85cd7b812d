"""Operations and bytes that one engine step's ALGORITHM needs for
Keye-VL-2.0's language model, from shapes and the step's own record
alone. The yardstick of ``lm_mfu_pct.longform`` and of the four roofline
shares of ``digest_keye_longform``: written for the work and not for the
form that does it (a mask over every causal key or a gather of the
chosen, bisection or a sort), so that a later change of form reads
against the same counts and none can pass 100%.

One step carries ``prefill`` real tokens of one request from position
``context`` on and one token of each decoding row at ``row_pos``. A
multiply-add is two operations. Per layer (all are alike):

- projections: q, k, v, o and the indexer's three (``qI``, ``kI``, the
  16 weights); the router; ``num_experts_per_tok`` routed experts;
- ``index``: a token at position ``p`` scores its ``p + 1`` causal keys:
  ``2 * heads * dim`` for the products and ``2 * heads`` for ReLU times
  weight, summed, per key. Bytes: each sequence's indexer keys once (2
  bytes a dim), the queries in, and nothing out (a fused choice would
  never write the scores);
- ``select``: the choice needs each score of a query that has more than
  ``topk`` keys once: one comparison an element and, since this
  program's scores do cross HBM, 4 bytes an element read once (the form
  that runs reads them some twenty times: that is what the share says);
- ``sparse``: attention over ``min(p + 1, topk)`` keys: ``4 * heads *
  head_dim`` a key. Bytes: per sequence the K and V of the keys it can
  have chosen (``min(keys in context, sum of its queries' choices)``), q
  in and the heads out;
- the head: one row per decoding row, and one for the chunk when it is
  the request's last.

Needed bytes of the whole step are what has to cross HBM at least once:
every weight used (of the experts those that hold a row), embedding
rows, the parts above, the new cache entries written, the logits out.
"""

from __future__ import annotations

W = 2           # bytes of a bfloat16 weight, K/V or indexer-key element


def per_token_flops(cfg: dict) -> dict:
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    sa = cfg["sa_config"]
    index_out = sa["indexer_num_heads"] * (sa["indexer_head_dim"] + 1) \
        + sa["indexer_head_dim"]
    return {"projections": 2 * h * (q + 2 * kv) + 2 * q * h
            + 2 * h * index_out,
            "router": 2 * h * cfg["num_experts"],
            "experts": cfg["num_experts_per_tok"] * 2 * 3 * h
            * cfg["moe_intermediate_size"],
            "head_row": 2 * h * cfg["vocab_size"]}


def _sequences(prefill: int, context: int, row_pos: list[int]
               ) -> list[tuple[int, int]]:
    """``(first query position, queries)`` of each sequence a step
    carries."""
    out = [(p, 1) for p in row_pos]
    if prefill > 0:
        out.append((context, prefill))
    return out


def step_cost(cfg: dict, *, prefill: int, context: int, row_pos: list[int],
              last_chunk: bool, experts_held: list[int] | None = None
              ) -> dict:
    """``{"flops", "bytes", "parts": {"experts", "index", "select",
    "sparse"}}`` of one step; each part ``{"flops", "bytes"}`` summed
    over the layers."""
    layers = cfg["num_hidden_layers"]
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    ih, idim, top = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                     sa["topk"])
    i_moe, e, k = (cfg["moe_intermediate_size"], cfg["num_experts"],
                   cfg["num_experts_per_tok"])
    tokens = prefill + len(row_pos)
    head_rows = len(row_pos) + (1 if prefill and last_chunk else 0)
    per = per_token_flops(cfg)
    if experts_held is None:
        experts_held = [min(e, tokens * k)] * layers

    causal = chosen = over = 0          # keys summed over the queries
    index_keys = sparse_keys = 0        # distinct keys read, by sequence
    for first, n in _sequences(prefill, context, row_pos):
        last = first + n - 1
        seq_causal = n * first + n * (n + 1) // 2
        # queries at positions below ``top`` keep every key they have
        short = max(0, min(n, top - first))
        seq_chosen = (short * first + short * (short + 1) // 2
                      + (n - short) * top)
        causal += seq_causal
        chosen += seq_chosen
        # scores of the queries that have to choose
        over += seq_causal - (short * first + short * (short + 1) // 2)
        index_keys += last + 1
        sparse_keys += min(last + 1, seq_chosen)
    index = {"flops": float(layers * causal * (2 * ih * idim + 2 * ih)),
             "bytes": float(layers * (index_keys * idim * W
                                      + tokens * ih * (idim * W + 4)))}
    select = {"flops": float(layers * over), "bytes": float(layers * over * 4)}
    sparse = {"flops": float(layers * chosen * 4 * nh * hd),
              "bytes": float(layers * (2 * sparse_keys * nkv * hd * W
                                       + 2 * tokens * nh * hd * W))}
    pairs = tokens * k
    experts = {"flops": float(layers * tokens * per["experts"]),
               "bytes": float(sum(held * 3 * h * i_moe * W
                                  for held in experts_held)
                              + layers * (pairs * h * W + pairs * h * 4))}
    linear = tokens * layers * (per["projections"] + per["router"])
    flops = linear + experts["flops"] + index["flops"] + select["flops"] \
        + sparse["flops"] + head_rows * per["head_row"]

    q, kvw = nh * hd, nkv * hd
    weights = layers * (h * (q + 2 * kvw) + q * h
                        + h * (ih * (idim + 1) + idim) + h * e) * W
    if head_rows:
        weights += h * cfg["vocab_size"] * W
    moved = (tokens * h * W                                 # embedding rows
             + layers * tokens * (2 * kvw + idim) * W       # cache written
             + head_rows * cfg["vocab_size"] * 4)           # logits out
    nbytes = weights + moved + index["bytes"] + sparse["bytes"] \
        + experts["bytes"]
    return {"flops": float(flops), "bytes": float(nbytes),
            "parts": {"experts": experts, "index": index, "select": select,
                      "sparse": sparse, "linear_flops": float(linear),
                      "weight_bytes": float(weights)},
            "keys": {"causal": causal, "chosen": chosen}}


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
