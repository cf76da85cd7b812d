"""Mean over the window's ticks of one number of the engine's own tick
record (``batch_log`` entries, ``asr/engine.py``): ``field`` names a
key of the record in seconds (``gap_s``: the stretch in which the device
held no beam program of the engine's), ``phase`` a key of its
``phase_s`` (``device_wait``, ``mel``, ``dispatch``, ...). Ticks whose
record holds ``None`` there (the first tick's gap) are left out; an
engine without the record gives ``None``."""


def read(ctx, field=None, phase=None, **_):
    if (field is None) == (phase is None):
        raise ValueError("tick_record_ms takes one of field= and phase=")
    values = []
    for b in ctx["batch_log"]:
        v = b.get("phase_s", {}).get(phase) if phase else b.get(field)
        if v is not None:
            values.append(v)
    if not values:
        return None
    return 1000.0 * sum(values) / len(values)
