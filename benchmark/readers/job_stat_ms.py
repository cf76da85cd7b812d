"""Mean over the jobs that began inside the window of one stage's
seconds as the entry itself records them in ``stats_out``
(``worker/transcribe.py``: ``language_pass_s``, ``vad_s``, ``served_s``,
``stitch_s``). ``None`` where no such job has the key: an entry that
records none, or a window in which no restarted job got that far."""


def read(ctx, key, **_):
    w = ctx["window"]
    values = [j.stats[key] for j in ctx["jobs"]
              if w["t0"] <= j.start_t <= w["t_end"]
              and j.stats.get(key) is not None]
    if not values:
        return None
    return 1000.0 * sum(values) / len(values)
