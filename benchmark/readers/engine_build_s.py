"""Seconds the engine's tick thread spent building programs over the
whole process: jaxpr tracing, lowering and backend compile (a cache
hit's retrieval included) as the program's own meter books them by
thread (``parallel/compile_cache.py::build_seconds``). It is the part of
``setup_s`` that only the program can shorten; any of it inside the
window is a recompile and shows in that tick's record as ``build_s``.
``None`` from a program without the meter."""

THREAD = "vlog-asr-engine"


def read(ctx, **_):
    try:
        from vlog_tpu.parallel import compile_cache
    except ImportError:
        return None
    meter = getattr(compile_cache, "build_seconds", None)
    if meter is None:
        return None
    phases = meter().get(THREAD)
    if not phases:
        return None
    return phases["trace"] + phases["lower"] + phases["compile"]
