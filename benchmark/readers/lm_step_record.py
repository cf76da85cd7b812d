"""One number of the step engine's own step records (``step_log``
entries, ``lm/engine.py``) over the window's steps: ``field`` names a
key of the record (``step_s``, ``gap_s``, ``decode_rows``,
``prefill_tokens``), ``stat`` is ``mean`` or ``median``, ``scale``
multiplies (1000 for milliseconds). Records that hold ``None`` there are
left out; a program without the record gives ``None``."""

import statistics


def read(ctx, field, stat="mean", scale=1.0, **_):
    values = [r[field] for r in ctx.get("step_log") or ()
              if r.get(field) is not None]
    if not values:
        return None
    agg = statistics.median if stat == "median" else statistics.mean
    return scale * float(agg(values))
