"""One host for the worker's model engines.

A worker's chip holds ONE model engine at a time: a Whisper beam program
and the transcript model's weights do not share its memory. Which
engine that is, who builds the next one and when the resident one must
go is decided here and nowhere else. The planes (``asr/engine.py``,
``lm/engine.py``) keep the few lines that know how to load their assets
and what their key is, and go through :data:`HOST`; this module imports
neither, and tells planes apart only by the name they give.

What an engine must offer the host: ``active() -> bool`` (serving now:
queued work, or the mesh lease held) and ``close()``, which also frees
whatever the engine put on the device.

:class:`HeldLease` is the other thing both engines held twice: ONE
``MeshScheduler`` ticket for the engine's thread, taken when there is
work and given back when the engine decides to (each engine keeps its
own rule for WHEN).
"""

from __future__ import annotations

import threading
import time
from collections.abc import Callable, Hashable
from typing import Any

from vlog_tpu.parallel.scheduler import SlotCancelled


class EngineHost:
    """The registry of resident engines, by plane name."""

    def __init__(self, *, room_timeout_s: float = 3600.0,
                 poll_s: float = 0.05) -> None:
        self.room_timeout_s = room_timeout_s
        self.poll_s = poll_s
        # one build at a time across all planes: two jobs claimed
        # together would otherwise both load the weights, and two copies
        # of 8.6 GB pass the chip's 16
        self._build_lock = threading.Lock()         # lock-order: 14
        self._lock = threading.Lock()               # lock-order: 16
        # plane -> (key, engine)
        # guarded-by: _lock
        self._resident: dict[str, tuple[Hashable, Any]] = {}

    def peek(self, plane: str) -> Any | None:
        """The plane's resident engine, or None; never builds one."""
        with self._lock:
            held = self._resident.get(plane)
        return None if held is None else held[1]

    def _built_for(self, plane: str, key: Hashable) -> Any | None:
        """The plane's resident engine if it was built for ``key``."""
        with self._lock:
            held = self._resident.get(plane)
        return held[1] if held is not None and held[0] == key else None

    def active(self, plane: str) -> bool:
        """Is the plane's engine serving? Never builds it (an idle
        worker must not page in weights from its claim loop)."""
        engine = self.peek(plane)
        return engine is not None and engine.active()

    def evict(self, plane: str) -> None:
        """Close the plane's engine, busy or not (the plane's own
        ``reset_engine``; a key that changed)."""
        with self._lock:
            held = self._resident.pop(plane, None)
        if held is not None:
            held[1].close()

    def obtain(self, plane: str, key: Hashable,
               build: Callable[[], Any]) -> Any:
        """The plane's resident engine if it was built for ``key``; else
        the old one is closed, every OTHER plane's engine is waited on
        until it is idle (a job boundary: nothing queued, no lease held)
        and closed, and ``build()`` makes the new one. Raises
        ``TimeoutError`` if another plane stays busy (its jobs hold the
        chip; the caller's job fails and is retried)."""
        engine = self._built_for(plane, key)
        if engine is not None:
            return engine
        with self._build_lock:
            engine = self._built_for(plane, key)
            if engine is not None:
                return engine       # built while this caller waited
            self.evict(plane)
            with self._lock:
                others = [p for p in self._resident if p != plane]
            for other in others:
                deadline = time.monotonic() + self.room_timeout_s
                while self.active(other):
                    if time.monotonic() >= deadline:
                        raise TimeoutError(
                            f"the {other} engine stayed busy for "
                            f"{self.room_timeout_s:.0f} s")
                    time.sleep(self.poll_s)  # holds-ok: this lock IS the one-build-at-a-time serializer; no tick or step takes it
                self.evict(other)
            engine = build()
            with self._lock:
                self._resident[plane] = (key, engine)
            return engine


HOST = EngineHost()


class HeldLease:
    """An engine thread's hold on the mesh: the ticket, the lease and
    the ``held`` event that the engine's ``active()`` reads. Without a
    scheduler (CLI, the benchmark) there is nothing to hold and
    :meth:`acquire` is always True."""

    def __init__(self, scheduler) -> None:
        self.scheduler = scheduler
        self.held = threading.Event()       # read from other threads
        # engine thread only
        self._ticket = None
        self.lease = None

    def acquire(self, stop: threading.Event) -> bool:
        """Hold a slot lease where a scheduler hands them out; False if
        ``stop`` fired while waiting."""
        if self.scheduler is None or self.lease is not None:
            return True
        self._ticket = self.scheduler.admit()
        try:
            self.lease = self._ticket.acquire(cancel=stop)
        except SlotCancelled:
            self.release()
            return False
        self.held.set()
        return True

    def release(self) -> None:
        if self._ticket is not None:
            self._ticket.close()        # releases the lease too
        self._ticket = None
        self.lease = None
        self.held.clear()

    def yield_full_mesh(self) -> None:
        """Work-conserving: a full-mesh fallback lease goes back as soon
        as other demand queues (the next acquire gets a slot)."""
        if (self.lease is not None and self.lease.is_full_mesh
                and self.scheduler.snapshot()["pending"] > 0):
            self.release()
