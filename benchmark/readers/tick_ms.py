"""Mean wall time of an engine tick over the window, from the engine's
own ``batch_log`` (``elapsed_s``: a host clock around mel, the beam
program, the token pull and the parse)."""


def read(ctx, **_):
    log = ctx["batch_log"]
    if not log:
        return None
    return 1000.0 * sum(b["elapsed_s"] for b in log) / len(log)
