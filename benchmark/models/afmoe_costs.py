"""Operations and bytes that one engine step's ALGORITHM needs, from
shapes and the step's own record alone. The yardstick of
``lm_mfu_pct.digest``, ``lm_moe_roofline.digest`` and
``lm_attn_roofline.digest``: it does not change with what implements
the step (XLA loops, ``ragged_dot``'s kernel, a Pallas kernel later), so
a faster program reads a higher share and none can pass 100%.

One step carries ``prefill`` real tokens of one request from position
``context`` on and one token of each of ``decode_rows`` rows at the
positions ``row_pos``. A multiply-add is two operations.

- per token and layer: the five attention projections (q, k, v, the
  output gate, o), then the dense SwiGLU (layers below
  ``num_dense_layers``) or the router, ``num_experts_per_tok`` routed
  experts and the shared one;
- attention of a token at position ``p``: ``4 * heads * head_dim`` per
  visible key; ``p + 1`` keys on a full layer, ``min(p + 1, window)`` on
  a window layer: the BAND's work, whatever the program visits;
- the head: one row per decoding row, and one for the chunk when it is
  the request's last.

Needed bytes are what has to cross HBM at least once: every weight that
is used once (of the experts only those that hold a row,
``experts_held`` from the step's record), the embedding rows, each
sequence's visible K/V once per layer plus the new entries written, and
the logits out. Activations between layers are taken to stay on chip.
"""

from __future__ import annotations

SLIDING = "sliding_attention"
W = 2           # bytes of a bfloat16 weight or K/V element


def per_token_flops(cfg: dict) -> dict:
    """Operations one token needs outside attention's keys, by part."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    i = cfg["moe_intermediate_size"]
    return {"projections": 2 * h * (q + 2 * kv + q) + 2 * q * h,
            "dense_mlp": 2 * 3 * h * cfg["intermediate_size"],
            "router": 2 * h * cfg["num_experts"],
            "experts": cfg["num_experts_per_tok"] * 2 * 3 * h * i,
            "shared": cfg["num_shared_experts"] * 2 * 3 * h * i,
            "head_row": 2 * h * cfg["vocab_size"]}


def visible_keys(pos: int, window: int | None) -> int:
    return pos + 1 if window is None else min(pos + 1, window)


def _span_keys(first: int, n: int, window: int | None) -> tuple[int, int]:
    """``(sum over the n queries from first on of their visible keys,
    distinct keys they read between them)``."""
    if n <= 0:
        return 0, 0
    total = sum(visible_keys(p, window) for p in range(first, first + n))
    last = first + n - 1
    low = 0 if window is None else max(0, first - window + 1)
    return total, last - low + 1


def step_cost(cfg: dict, *, prefill: int, context: int,
              row_pos: list[int], last_chunk: bool,
              experts_held: list[int] | None = None) -> dict:
    """``{"flops", "bytes", "parts": {"experts": {...}, "attn": {...}}}``
    of one step. ``experts_held``: per expert layer, the experts that
    held a row (default: all that the step's pairs can reach)."""
    layers = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][:layers]
    n_dense = cfg["num_dense_layers"]
    n_moe = layers - n_dense
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    i_moe, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    k = cfg["num_experts_per_tok"]
    tokens = prefill + len(row_pos)
    head_rows = len(row_pos) + (1 if prefill and last_chunk else 0)
    per = per_token_flops(cfg)
    if experts_held is None:
        experts_held = [min(e, tokens * k)] * n_moe

    attn_flops = attn_bytes = 0
    for kind in kinds:
        window = cfg["sliding_window"] if kind == SLIDING else None
        keys, distinct = _span_keys(context, prefill, window)
        for p in row_pos:
            keys += visible_keys(p, window)
            distinct += visible_keys(p, window)
        attn_flops += 4 * nh * hd * keys
        # K and V of every visible key once, q in and the heads out
        attn_bytes += 2 * distinct * nkv * hd * W \
            + 2 * tokens * nh * hd * W
    experts_flops = n_moe * tokens * per["experts"]
    pairs = tokens * k
    experts_bytes = sum(held * 3 * h * i_moe * W for held in experts_held) \
        + n_moe * (pairs * h * W              # the sorted rows in
                   + pairs * h * 4)           # their float32 results out

    linear = tokens * (layers * per["projections"]
                       + n_dense * per["dense_mlp"]
                       + n_moe * (per["router"] + per["shared"]))
    flops = linear + experts_flops + attn_flops + head_rows * per["head_row"]

    q = nh * hd
    kvw = nkv * hd
    weights = (layers * (h * (2 * q + 2 * kvw) + q * h)
               + n_dense * 3 * h * cfg["intermediate_size"]
               + n_moe * (h * e + cfg["num_shared_experts"] * 3 * h * i_moe)
               ) * W
    if head_rows:
        weights += h * cfg["vocab_size"] * W
    moved = (tokens * h * W                             # embedding rows
             + layers * tokens * 2 * kvw * W            # K/V written
             + head_rows * cfg["vocab_size"] * 4)       # logits out
    nbytes = weights + moved + attn_bytes + experts_bytes
    return {"flops": float(flops), "bytes": float(nbytes),
            "parts": {"experts": {"flops": float(experts_flops),
                                  "bytes": float(experts_bytes)},
                      "attn": {"flops": float(attn_flops),
                               "bytes": float(attn_bytes)},
                      "linear_flops": float(linear),
                      "weight_bytes": float(weights)}}


def record_cost(cfg: dict, record: dict) -> dict:
    """:func:`step_cost` of one step record of the engine
    (``lm/engine.py``; the rows' positions ride in ``row_pos``)."""
    held = [int(x[2]) for x in record["expert_load"]] \
        if record.get("expert_load") and len(record["expert_load"][0]) > 2 \
        else None
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
