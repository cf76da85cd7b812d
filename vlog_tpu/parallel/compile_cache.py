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
   report a fraction of cold ones).
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

# vlog_tpu/_xla_cache, beside the native coders' _build/ (git-ignored)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / "_xla_cache"

# _state and _meter are only read/written under _lock (module-level
# singletons, so the guarded-by annotation idiom for instance fields
# does not apply here).
_lock = threading.Lock()
_state: dict = {"armed": False, "dir": None}
_meter: dict = {"registered": False, "seconds": 0.0}

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        with _lock:
            _meter["seconds"] += float(duration)


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
