"""The tick's program in a trace: the compiled module that took most
device time, with its runs inside the trace. Not looked up by name, so
a renamed or re-fused program is still found."""


def main_module(trace):
    mods = trace["modules"] if trace else {}
    if not mods:
        return None
    name = max(mods, key=lambda k: mods[k]["seconds"])
    return name, mods[name]


def rows_per_tick(ctx):
    """Real windows per tick over the window (the needed work is that of
    the windows served, not of padded rows)."""
    log = ctx["batch_log"]
    return sum(b["n"] for b in log) / len(log) if log else None


def run_and_cost(ctx):
    """``(device seconds of one run of the tick's program, needed cost of
    an average tick)``, or ``None`` where the trace, the ticks or the
    peaks give nothing to read."""
    found = main_module(ctx["trace"])
    n = rows_per_tick(ctx)
    if not found or not n or ctx["tick_cost"] is None or not ctx["peaks"]:
        return None
    _name, m = found
    if not m["runs"] or m["seconds"] <= 0:
        return None
    return m["seconds"] / m["runs"], ctx["tick_cost"](n)
