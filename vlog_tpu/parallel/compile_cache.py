"""Shared persistent-compile-cache plumbing + per-process compile meter.

Cold-compile elimination has two halves:

1. **Persistence.** The XLA programs for 4K chain ladders take minutes
   to compile; jax's persistent compilation cache amortizes that across
   worker restarts. Every codec backend and the ASR engine call
   :func:`ensure_compile_cache` before first dispatch.

   Where the cache lives is decided outside the program first:

   - ``JAX_COMPILATION_CACHE_DIR`` set: jax itself reads that variable;
     this module sets NO directory in code and only reports it.
   - either way every program persists (the min-compile-time floor is
     dropped to zero), so a warm start recompiles nothing.
   - unset, on an accelerator: one fixed path inside the checkout,
     :data:`DEFAULT_CACHE_DIR`, resolved from this package's own
     location. The path is part of nothing that moves (cwd, BASE_DIR,
     pid), so a restarted worker finds what the last one compiled.
   - unset, on CPU: no cache. CPU AOT entries record exact host ISA
     features and reloading them on a different machine risks SIGILL.

2. **Attribution.** ``compile_seconds()`` meters this process's
   cumulative backend-compile wall time via ``jax.monitoring``'s
   ``/jax/core/compile/backend_compile_duration`` events (a persistent-
   cache HIT skips the backend compile entirely, so warm processes
   report a fraction of cold ones). ``build_seconds()`` says which step
   rebuilt and what the rebuild was: the same listener books, per
   thread it was called on (jax calls it in the thread that builds),

   - ``trace``: ``/jax/core/compile/jaxpr_trace_duration``, a jitted
     function's Python body run to a jaxpr. A jit traced inside another
     one's trace reports too; its seconds are taken off the outer
     event's, so nothing is booked twice;
   - ``lower``: ``/jax/core/compile/jaxpr_to_mlir_module_duration``;
   - ``compile``: ``backend_compile_duration``, which in this jax
     (0.9.0) wraps ``compile_or_get_cached`` and so holds a hit's
     retrieval too;
   - ``cache_load``: ``/jax/compilation_cache/cache_retrieval_time_sec``,
     the part of ``compile`` that read a persistent-cache hit.

   The listener is registered by the first call of either function, so
   a process that never arms the cache (the benchmark's) meters too.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from pathlib import Path

# vlog_tpu/_xla_cache, beside the native coders' _build/ (git-ignored)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / "_xla_cache"

# _state and _meter are only read/written under _lock (module-level
# singletons, so the guarded-by annotation idiom for instance fields
# does not apply here).
_lock = threading.Lock()
_state: dict = {"armed": False, "dir": None}
_meter: dict = {"registered": False, "seconds": 0.0, "by_thread": {},
                "open_traces": {}}

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
BUILD_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _COMPILE_EVENT: "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_now = time.monotonic    # the listener's clock (tests give it their own)
# a trace event stays here until the event of the jit that enclosed it
# comes (or 64 later ones have: a top-level trace has no such event)
_OPEN_TRACES = 64


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    phase = BUILD_PHASES.get(event)
    if phase is None:
        return
    duration = float(duration)
    now = _now()
    thread = threading.current_thread().name
    with _lock:
        if event == _COMPILE_EVENT:
            _meter["seconds"] += duration
        booked = duration
        if phase == "trace":
            # events end innermost first: what began inside this one's
            # stretch on this thread is already booked
            started = now - duration
            inner = _meter["open_traces"].setdefault(
                thread, deque(maxlen=_OPEN_TRACES))
            while inner and inner[-1][0] >= started:
                booked -= inner.pop()[1]
            inner.append((started, duration))
            booked = max(booked, 0.0)
        phases = _meter["by_thread"].setdefault(
            thread, dict.fromkeys(BUILD_PHASES.values(), 0.0))
        phases[phase] += booked


def _register_meter_locked() -> None:
    if _meter["registered"]:
        return
    _meter["registered"] = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)


def compile_seconds() -> float:
    """Cumulative XLA backend-compile seconds metered this process (0.0
    until :func:`ensure_compile_cache` or a bench arms the listener)."""
    with _lock:
        _register_meter_locked()
        return _meter["seconds"]


def build_seconds() -> dict[str, dict[str, float]]:
    """``{thread name: {"trace", "lower", "compile", "cache_load"}}``:
    seconds this process spent building programs, by the thread that
    built them (module docstring). ``cache_load`` lies inside
    ``compile``; :func:`build_total` adds the other three."""
    with _lock:
        _register_meter_locked()
        return {t: dict(p) for t, p in _meter["by_thread"].items()}


def thread_build_seconds() -> dict[str, float]:
    """The calling thread's entry of :func:`build_seconds` (zeros if it
    has built nothing): read before and after a stretch of work, the
    difference is what that stretch spent building programs."""
    name = threading.current_thread().name
    with _lock:
        _register_meter_locked()
        return dict(_meter["by_thread"].get(name)
                    or dict.fromkeys(BUILD_PHASES.values(), 0.0))


def build_total(phases: dict[str, float]) -> float:
    """Seconds of one thread's entry with nothing counted twice
    (``cache_load`` lies inside ``compile``)."""
    return phases["trace"] + phases["lower"] + phases["compile"]


def thread_built_s() -> float:
    """Seconds the calling thread has spent building programs so far."""
    return build_total(thread_build_seconds())


def ensure_compile_cache() -> str | None:
    """Arm the persistent compile cache (idempotent); returns the cache
    dir in effect, or None when this platform runs without one."""
    with _lock:
        _register_meter_locked()
        if _state["armed"]:
            return _state["dir"]
        _state["armed"] = True
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    if cache_dir is None and jax.devices()[0].platform != "cpu":
        DEFAULT_CACHE_DIR.mkdir(exist_ok=True)
        cache_dir = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # jax binds its cache object at most once per process; a compile
        # that ran before this call bound it to "no cache".
        from jax.experimental.compilation_cache import (
            compilation_cache as _jcc)

        _jcc.reset_cache()
    if cache_dir is not None:
        # Persist every program, not only those over jax's 1 s floor: a
        # warm worker otherwise recompiles its hundreds of sub-second
        # programs (56 s of a 205 s cold start on the chip, PR 21).
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with _lock:
        _state["dir"] = cache_dir
    return cache_dir


def reset_for_tests() -> None:
    """Forget armed state + meter (unit tests re-arm with fresh knobs)."""
    with _lock:
        _state["armed"] = False
        _state["dir"] = None
        _meter["seconds"] = 0.0
        _meter["by_thread"] = {}
        _meter["open_traces"] = {}
