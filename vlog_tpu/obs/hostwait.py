"""Who kept the chip waiting: the host's wait on a device result, the
process's garbage-collection pauses, and the stalls an engine saw.

Three pieces, shared by both model engines (``lm/engine.py``,
``asr/engine.py`` through ``asr/decode.py``) and cheap enough to stay on
in every run:

- :data:`GC`, the process's :class:`GcRecorder`: one ``gc.callbacks``
  entry, installed when the first engine's thread starts. It keeps a bounded ring of ``(start, seconds,
  generation)`` on ``time.monotonic()`` (the clock of the engines'
  records) and the seconds by generation (``vlog_gc_pause_seconds_total``
  reads them at scrape time). A collection of generation 1 or 2 also
  opens a ``vlog:gc.gen<N>`` annotation for its length under
  ``obs/trace.py``'s rule (only where jax is imported), so a profile
  puts the pause on the device trace's clock; generation 0 runs hundreds
  of times a second and gets none.
- :func:`pull`: wait for device arrays by polling ``is_ready()``, copy
  them to the host, and say how the wait went (the wait record):
  ``polls`` (``is_ready()`` calls), ``gap_max_s`` (the longest stretch in
  which the waiting thread did not come back: between two polls, from
  the start to the first, from the last to the end of the copy),
  ``cpu_s`` (the thread's own CPU seconds), ``gc_s`` (collection seconds
  that overlapped the wait), ``wait_s``, and where a long gap was spent:
  ``ready_max_s`` (the longest single ``is_ready()`` call) and ``copy_s``
  (the copy to the host). A gap that neither holds is the thread waiting
  to run again (the GIL, the OS) after its sleep.
- :class:`WaitBook`: an engine's waits. A wait that exceeds the median of
  the last 64 of its key by ``STALL_S`` or more is a stall, booked to
  ``gc`` where collections cover half the excess, to ``runtime`` where
  one ``is_ready()`` call or the copy does, to ``host`` where the thread
  was away that long at a stretch outside them, else to ``runtime`` (the
  device or its runtime said "not ready"); the five longest waits of the
  engine's life are kept with their records.
"""

from __future__ import annotations

import gc
import statistics
import time
from collections import deque

from vlog_tpu.obs.trace import _annotation

__all__ = ["GC", "GcRecorder", "pull", "WaitBook", "classify", "STALL_S"]

RING = 4096             # collections kept; gen 0 alone runs ~10^2 a second
STALL_S = 0.2           # excess over the recent median that is a stall
HISTORY = 64            # recent waits of one key the median is taken over
KEEP = 5                # longest waits an engine keeps
CAUSES = ("gc", "host", "runtime")


class GcRecorder:
    """The process's collections, from ``gc.callbacks``.

    The callback runs in whichever thread collects, with the GIL held and
    no collection nested in it: it takes no lock and touches no metric
    object (a prometheus counter's lock may be held by the very thread
    that the collection interrupted). The ring is a fixed list written
    at a counter, so a reader never iterates a container that a
    collection in another thread could grow under it."""

    def __init__(self, size: int = RING) -> None:
        self.size = size
        self._ring: list[tuple[float, float, int] | None] = [None] * size
        self._n = 0                         # collections ever recorded
        self.seconds = [0.0, 0.0, 0.0]      # by generation
        self.counts = [0, 0, 0]
        self._start: float | None = None
        self._open = None                   # the annotation of gen 1 / 2
        self._installed = False

    def _callback(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        if phase == "start":
            if gen:
                self._open = _annotation(f"gc.gen{gen}", {})
                if self._open is not None:
                    self._open.__enter__()
            self._start = time.monotonic()
            return
        start, self._start = self._start, None
        if start is None:                   # installed mid-collection
            return
        seconds = time.monotonic() - start
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        self._ring[self._n % self.size] = (start, seconds, gen)
        self._n += 1
        self.seconds[gen] += seconds
        self.counts[gen] += 1

    def install(self) -> None:
        """Start recording (idempotent: one callback a process)."""
        if not self._installed:
            self._installed = True
            gc.callbacks.append(self._callback)

    def reset(self) -> None:
        """Stop recording and forget everything (tests)."""
        if self._callback in gc.callbacks:
            gc.callbacks.remove(self._callback)
        self._installed = False
        self._ring = [None] * self.size
        self._n = 0
        self.seconds = [0.0, 0.0, 0.0]
        self.counts = [0, 0, 0]
        self._start = self._open = None

    def entries(self) -> list[tuple[float, float, int]]:
        """The ring, oldest first: at most ``size`` collections."""
        n = self._n
        return [self._ring[k % self.size]
                for k in range(max(0, n - self.size), n)]

    def seconds_between(self, t0: float, t1: float) -> float:
        """Collection seconds that overlapped ``[t0, t1]`` (monotonic),
        scanning back from the newest until one ended before ``t0``."""
        total = 0.0
        n = self._n
        for k in range(n - 1, max(-1, n - self.size - 1), -1):
            start, seconds, _gen = self._ring[k % self.size]
            if start + seconds < t0:
                break
            total += max(0.0, min(t1, start + seconds) - max(t0, start))
        return total


GC = GcRecorder()


def pull(arrays, *, poll_s: float) -> tuple[tuple, dict]:
    """Wait until every array of ``arrays`` is ready, polling
    ``is_ready()`` every ``poll_s`` seconds (a thread that sleeps holds
    no GIL), then copy them to the host. Returns the host arrays and the
    wait record (module docstring). An object without ``is_ready`` (a
    stand-in's numpy array) counts as ready."""
    import numpy as np

    t0 = last = time.monotonic()
    cpu0 = time.thread_time()
    polls = 0
    gap = ready_max = 0.0
    for a in arrays:
        ready = getattr(a, "is_ready", None)
        while True:
            now = time.monotonic()
            gap, last = max(gap, now - last), now
            polls += 1
            if ready is None:
                break
            done = ready()
            ready_max = max(ready_max, time.monotonic() - now)
            if done:
                break
            time.sleep(poll_s)
    copied = time.monotonic()
    host = tuple(np.asarray(a) for a in arrays)
    end = time.monotonic()
    return host, {"polls": polls, "gap_max_s": max(gap, end - last),
                  "cpu_s": time.thread_time() - cpu0,
                  "gc_s": GC.seconds_between(t0, end), "wait_s": end - t0,
                  "ready_max_s": ready_max, "copy_s": end - copied}


def classify(wait: dict, excess_s: float) -> str:
    """The cause of a stall whose wait exceeded the recent median by
    ``excess_s``: ``gc`` if collections cover half of it, ``runtime`` if
    one ``is_ready()`` call or the copy to the host does (a gap holds the
    call it ended with, so the gap alone cannot tell the thread's absence
    from a call that blocked; the thread taking the GIL back after the
    call reads as the call), ``host`` if the waiting thread was away that
    long at a stretch outside them, else ``runtime`` (many polls, each
    "not ready")."""
    half = excess_s / 2
    if wait["gc_s"] >= half:
        return "gc"
    if max(wait["ready_max_s"], wait["copy_s"]) >= half:
        return "runtime"
    if wait["gap_max_s"] >= half:
        return "host"
    return "runtime"


class WaitBook:
    """One engine's waits (the engine serialises calls under its lock).
    ``key`` separates waits of different programs (the LM's chunk
    bucket, the ASR tick's rows): a 2,048-token chunk is no stall beside
    decode-only steps."""

    def __init__(self) -> None:
        self._recent: dict[object, deque] = {}
        self.count = 0
        self.stalls = dict.fromkeys(CAUSES, 0)
        self.longest: list[dict] = []

    def add(self, wait: dict, *, key: object, **about: object) -> str | None:
        """Book one wait record; returns the stall's cause or None."""
        recent = self._recent.setdefault(key, deque(maxlen=HISTORY))
        excess = (wait["wait_s"] - statistics.median(recent)) if recent \
            else None
        recent.append(wait["wait_s"])
        cause = (classify(wait, excess)
                 if excess is not None and excess >= STALL_S else None)
        self.count += 1
        if cause is not None:
            self.stalls[cause] += 1
        if len(self.longest) < KEEP \
                or wait["wait_s"] > self.longest[-1]["wait_s"]:
            self.longest.append({**about, "key": key, **wait,
                                 "excess_s": excess, "cause": cause})
            self.longest.sort(key=lambda w: -w["wait_s"])
            del self.longest[KEEP:]
        return cause

    def stats(self) -> dict:
        """``count`` waits, ``stalls`` by cause, the ``longest`` five with
        their records, and beside them the process's collections so far
        (``process_gc``: seconds and collections by generation)."""
        return {"count": self.count, "stalls": dict(self.stalls),
                "longest": [dict(w) for w in self.longest],
                "process_gc": {"seconds": list(GC.seconds),
                               "collections": list(GC.counts)}}
