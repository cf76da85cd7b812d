"""Per finished job, the benchmark's span around the entry minus the
stretch its windows spent queued and in ticks (first submit to last
delivery): VAD gate, language pass, stitching, VTT. Mean over jobs."""


def read(ctx, **_):
    over = []
    for j in ctx["jobs"]:
        if j.status != "ok" or j.first_submit_t is None or not j.deliveries:
            continue
        served = max(t for _i, t, _w, _c in j.deliveries) - j.first_submit_t
        over.append((j.end_t - j.start_t) - served)
    if not over:
        return None
    return 1000.0 * sum(over) / len(over)
