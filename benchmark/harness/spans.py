"""The benchmark's own spans, recorded around calls into each layer.

Spans live in memory (name, thread, start, end on ``time.monotonic``,
optional payload) and, while the profiler runs, are mirrored into its
trace as ``TraceAnnotation``s named ``bench:<name>`` so that a device
idle gap can be labelled by what the host was doing, on one clock.
Nothing is written inside the program: :func:`wrap` swaps a module
attribute for a recording wrapper and :func:`unwrap_all` puts it back.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field


@dataclass
class Span:
    name: str
    thread: str
    t0: float
    t1: float
    data: dict = field(default_factory=dict)


class Recorder:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.spans: list[Span] = []
        self.annotate = False          # mirror into the profiler's trace
        self._wrapped: list[tuple[object, str, object]] = []

    def add(self, name: str, t0: float, t1: float, **data) -> Span:
        s = Span(name, threading.current_thread().name, t0, t1, data)
        with self._lock:
            self.spans.append(s)
        return s

    def reset(self) -> None:
        with self._lock:
            self.spans = []

    def named(self, name: str) -> list[Span]:
        with self._lock:
            return [s for s in self.spans if s.name == name]

    def span(self, name: str, **data):
        return _Ctx(self, name, data)

    def wrap(self, module, attr: str, name: str, capture=None) -> bool:
        """Record a span around every call of ``module.attr``.
        ``capture(args, kwargs, result) -> dict`` adds a payload.
        Returns False (and wraps nothing) where the attribute is gone."""
        orig = getattr(module, attr, None)
        if orig is None:
            return False
        rec = self

        def wrapper(*args, **kwargs):
            with rec.span(name) as ctx:
                out = orig(*args, **kwargs)
                if capture is not None:
                    ctx.data.update(capture(args, kwargs, out))
            return out

        wrapper.__wrapped__ = orig
        setattr(module, attr, wrapper)
        self._wrapped.append((module, attr, orig))
        return True

    def unwrap_all(self) -> None:
        for module, attr, orig in reversed(self._wrapped):
            setattr(module, attr, orig)
        self._wrapped = []


class _Ctx:
    def __init__(self, rec: Recorder, name: str, data: dict):
        self.rec, self.name, self.data = rec, name, dict(data)
        self._ann = None

    def __enter__(self):
        if self.rec.annotate:
            import jax

            self._ann = jax.profiler.TraceAnnotation(f"bench:{self.name}")
            self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.rec.add(self.name, self.t0, t1, **self.data)
        return False
