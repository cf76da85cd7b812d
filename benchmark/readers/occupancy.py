"""Share of batch rows that carried a real window: sum n / sum rows
over the window's ticks, from ``batch_log``. A count, unit ``1``."""


def read(ctx, **_):
    rows = sum(b["rows"] for b in ctx["batch_log"])
    if not rows:
        return None
    return sum(b["n"] for b in ctx["batch_log"]) / rows
