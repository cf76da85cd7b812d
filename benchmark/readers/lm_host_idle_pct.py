"""Device idle that the host caused, as a share of the traced stretch:
100 x the sum of the step records' ``idle_before_s`` (``lm/engine.py``:
from the first of the plan's checks that found the step in flight done
to this step's dispatch; a lower bound) over the steps of the traced
stretch (``trace_steps``), divided by the seconds of that stretch (the
capture's ``window_s``, which ``device_idle_pct`` divides by too, so the
two can be set against each other). A rehearsal takes no capture: there
it reads the window's steps over the window. Records without the key (a
program that does not check) give ``None``."""


def read(ctx, **_):
    trace = ctx.get("trace")
    if trace:
        steps, seconds = ctx.get("trace_steps") or (), trace["window_s"]
    else:
        window = ctx.get("window") or {}
        steps = ctx.get("step_log") or ()
        seconds = window.get("t_end", 0.0) - window.get("t0", 0.0)
    idle = [r["idle_before_s"] for r in steps
            if r.get("idle_before_s") is not None]
    if not idle or seconds <= 0:
        return None
    return 100.0 * sum(idle) / seconds
