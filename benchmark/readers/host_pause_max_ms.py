"""The longest stretch, in milliseconds, in which the engine's thread
did not come back while it waited for the device: the largest
``gap_max_s`` of the wait records (``wait``) on the window's step
records (``step_log``) or, without them, tick records (``batch_log``);
``obs/hostwait.py::pull``. Records without the key give ``None``."""


def read(ctx, **_):
    records = ctx.get("step_log") or ctx.get("batch_log") or ()
    gaps = [r["wait"]["gap_max_s"] for r in records if r.get("wait")]
    return 1000.0 * max(gaps) if gaps else None
