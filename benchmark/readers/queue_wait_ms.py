"""Mean time a window waited from ``enqueued_at`` to the start of its
tick, as the engine hands it back with every result (``wait_s``)."""


def read(ctx, **_):
    waits = [w for j in ctx["jobs"] for _i, _t, w, _c in j.deliveries]
    if not waits:
        return None
    return 1000.0 * sum(waits) / len(waits)
