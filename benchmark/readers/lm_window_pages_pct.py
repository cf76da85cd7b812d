"""Key pages a window layer visited over the pages a causal-full layer
would have visited for the same queries, summed over the window's
steps (both counted on the device). 100 would mean the window is only a
mask; the band of 2048 positions is what keeps it low on long
prompts."""


def read(ctx, **_):
    seen = would = 0
    for r in ctx.get("step_log") or ():
        if r.get("window_pages"):
            seen += r["window_pages"][0]
            would += r["window_pages"][1]
    if not would:
        return None
    return 100.0 * seen / would
