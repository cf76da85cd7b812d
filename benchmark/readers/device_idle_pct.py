"""1 minus the seconds in which a device operation ran over the traced
stretch of the window."""


def read(ctx, **_):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
