"""The profiler, and the reduction from its trace to numbers.

``Tracer`` turns ``jax.profiler`` on for a stretch of a traced run's
window that the driver chooses (a beam program writes 10^5 device events
a second, and ``stop_trace`` took 122 s for 20 s of them: my chip run,
PR 25) and off again, from a thread of the driver's or its own.
``reduce`` reads the ``.xplane.pb`` with nothing but JAX and returns
what the per-layer readers and the result line need:

- ``window_s``: first to last instant of the traced stretch;
- ``busy_s``: seconds in which a device OPERATION ran, averaged over
  the device planes: the leaves of the "XLA Ops" line (an op that
  contains others, a ``while`` around a scan's steps, is not counted
  on top of its children);
- ``modules``: per compiled program ("XLA Modules" line) its runs that
  lie wholly inside the trace and their device seconds;
- ``device_ops``: the ten operations that took most time;
- ``idle_gaps``: device gaps over 50 us, summed under the benchmark's
  own host span (``bench:<name>`` annotations) that covers their
  middle, the ten largest sums.

The same code reduces the small recorded trace kept beside the tests.
"""

from __future__ import annotations

import glob
import os
import shutil
import threading
import time
from pathlib import Path

GAP_NS = 50_000          # shorter device gaps are launch spacing, not idle
EDGE_NS = 1_000_000      # a program run this close to an end may be cut
ANNOTATION = "bench:"


class Tracer:
    def __init__(self, enabled: bool, log_dir: Path):
        self.enabled = enabled
        self.dir = Path(log_dir)
        self._lock = threading.Lock()
        self._on = False
        self._timer: threading.Thread | None = None
        self.stop_s = 0.0

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._on = True

    def trace_between(self, start: float, stop: float) -> None:
        """Trace from ``time.monotonic() == start`` to ``stop``, from a
        thread of its own: serialising the trace takes many seconds and
        the traffic goes on meanwhile."""
        if not self.enabled:
            return

        def wait():
            time.sleep(max(0.0, start - time.monotonic()))
            self.start()
            time.sleep(max(0.0, stop - time.monotonic()))
            self.stop_now()

        self._timer = threading.Thread(target=wait, name="bench-trace-stop",
                                       daemon=True)
        self._timer.start()

    def stop_now(self) -> None:
        with self._lock:
            if not self._on:
                return
            import jax

            t0 = time.monotonic()
            jax.profiler.stop_trace()
            self.stop_s = time.monotonic() - t0
            self._on = False

    def finish(self) -> dict | None:
        """Wait for the stop, reduce, delete the trace files."""
        if not self.enabled:
            return None
        if self._timer is not None:
            self._timer.join()
        self.stop_now()
        found = glob.glob(str(self.dir / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        try:
            if not found:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            out = reduce(found[0])
            out["trace_bytes"] = os.path.getsize(found[0])
            out["stop_trace_s"] = self.stop_s
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _short(name: str) -> str:
    """``%fusion.3 = (f32[..]) fusion(...)`` -> ``fusion.3``."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")[:120]


def _leaf_intervals(events: list[tuple[int, int, str]]
                    ) -> list[tuple[int, int, str]]:
    """Drop every event that contains the next one (sorted by start,
    longest first at equal starts): what is left does not nest."""
    events.sort(key=lambda e: (e[0], -(e[1] - e[0])))
    leaves = []
    for i, (s, e, name) in enumerate(events):
        if i + 1 < len(events) and events[i + 1][0] < e \
                and events[i + 1][1] <= e:
            continue
        leaves.append((s, e, name))
    return leaves


def reduce_planes(planes: list[dict]) -> dict:
    """``planes``: ``[{"name", "lines": [{"name", "events":
    [(start_ns, end_ns, name)]}]}]`` (what :func:`load` returns and what
    the recorded test trace holds)."""
    device = [p for p in planes if p["name"].startswith("/device:")
              and any(ln["name"] == "XLA Ops" and ln["events"]
                      for ln in p["lines"])]
    hosts = [p for p in planes if p["name"].startswith("/host:")]
    spans = sorted(
        (s, e, name[len(ANNOTATION):])
        for p in hosts for ln in p["lines"] for s, e, name in ln["events"]
        if name.startswith(ANNOTATION))

    lo = min((ev[0] for p in device for ln in p["lines"]
              for ev in ln["events"]), default=0)
    hi = max((ev[1] for p in device for ln in p["lines"]
              for ev in ln["events"]), default=0)
    for s, e, _ in spans:
        lo, hi = min(lo, s), max(hi, e)

    busy_ns = []
    op_ns: dict[str, int] = {}
    gap_ns: dict[str, int] = {}
    modules: dict[str, dict] = {}

    def label(at: int) -> str:
        best = None
        for s, e, name in spans:
            if s > at:
                break
            if e >= at and (best is None or e - s < best[0]):
                best = (e - s, name)
        return best[1] if best else "no_benchmark_span"

    for p in device:
        ops = next(ln for ln in p["lines"] if ln["name"] == "XLA Ops")
        leaves = _leaf_intervals(list(ops["events"]))
        busy = 0
        prev_end = lo
        for s, e, name in leaves:
            busy += e - s
            key = _short(name)
            op_ns[key] = op_ns.get(key, 0) + (e - s)
            if s - prev_end > GAP_NS:
                k = label((s + prev_end) // 2)
                gap_ns[k] = gap_ns.get(k, 0) + (s - prev_end)
            prev_end = max(prev_end, e)
        if hi - prev_end > GAP_NS:
            k = label((hi + prev_end) // 2)
            gap_ns[k] = gap_ns.get(k, 0) + (hi - prev_end)
        busy_ns.append(busy)
        for ln in p["lines"]:
            if ln["name"] != "XLA Modules":
                continue
            for s, e, name in ln["events"]:
                if s - lo < EDGE_NS or hi - e < EDGE_NS:
                    continue        # cut by the start or the stop
                m = modules.setdefault(name.split("(", 1)[0],
                                       {"runs": 0, "seconds": 0.0})
                m["runs"] += 1
                m["seconds"] += (e - s) / 1e9

    def top(d: dict[str, int]) -> list[list]:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    n = max(len(device), 1)
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_ns) / n / 1e9,
            "devices_traced": len(device),
            "modules": modules,
            "device_ops": top({k: v // n for k, v in op_ns.items()}),
            "idle_gaps": top({k: v // n for k, v in gap_ns.items()})}


def load(path: str) -> list[dict]:
    """The planes of an ``.xplane.pb`` that the reduction reads, as
    plain lists (device planes whole, host planes only their
    ``bench:`` annotations)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:")
        if not is_dev and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if is_dev and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            events = []
            for ev in line.events:
                if not is_dev and not ev.name.startswith(ANNOTATION):
                    continue
                s = int(ev.start_ns)
                events.append((s, s + int(ev.duration_ns), ev.name))
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def reduce(path: str) -> dict:
    return reduce_planes(load(path))
