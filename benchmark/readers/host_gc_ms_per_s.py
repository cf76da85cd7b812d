"""Milliseconds of garbage collection a second of the engine's cycles:
1000 x the sum of ``gc_s`` over the window's step records (``step_log``)
or, without them, tick records (``batch_log``), over the sum of their
``t_end - t_start`` (``obs/hostwait.py``: the process's collection
seconds inside each record's own stretch, every thread stopped).
Records without the key give ``None``."""


def read(ctx, **_):
    records = ctx.get("step_log") or ctx.get("batch_log") or ()
    got = [(r["gc_s"], r["t_end"] - r["t_start"]) for r in records
           if r.get("gc_s") is not None]
    span = sum(d for _g, d in got)
    if not got or span <= 0:
        return None
    return 1000.0 * sum(g for g, _d in got) / span
