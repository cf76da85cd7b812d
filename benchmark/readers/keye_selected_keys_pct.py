"""Keys a sparse-attention layer attended over the keys a causal-dense
layer would have attended for the same queries, summed over the window's
steps (both counted on the device: ``sparse_keys`` of the step record).
100 means every context was no longer than the selection; a playlist of
long recordings reads far below."""


def read(ctx, **_):
    seen = would = 0
    for r in ctx.get("step_log") or ():
        if r.get("sparse_keys"):
            seen += r["sparse_keys"][0]
            would += r["sparse_keys"][1]
    if not would:
        return None
    return 100.0 * seen / would
