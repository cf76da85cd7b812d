"""On-demand, duration-bounded device profiling sessions.

``POST /api/workers/{name}/profile`` (admin) queues a ``profile``
command on the worker command channel; the worker's next heartbeat tick
lands here and starts one ``jax.profiler.trace`` session writing a
TensorBoard-loadable artifact directory under ``VLOG_PROFILE_DIR``
(default ``BASE_DIR/profiles``). Sessions are:

- **duration-bounded** — the requested duration clamps to
  ``VLOG_PROFILE_MAX_S`` and a daemon timer thread stops the trace even
  if nobody ever asks again, so tracing can never be left on;
- **exclusive** — one active session per process (a second start is
  rejected, not queued);
- **contained** — session directories are created strictly inside the
  profile root (label characters are sanitized; the resolved path is
  verified under the resolved root before anything is written);
- **claim-epoch-safe** — the command rides the ordinary heartbeat
  command drain and touches no claim state, lease, or epoch: start and
  stop are millisecond registry calls on the heartbeat task, the
  bounded stop runs on its own daemon thread, and in-flight jobs keep
  running (their device work is exactly what the trace captures);
- **init-safe** — profiling requires JAX, but a management command must
  never *pay for* (or hang on) accelerator init, so start refuses
  unless the process has already imported jax (mgmt._device_info's
  rule). A worker that has not touched a device has nothing worth
  profiling anyway.

Outcomes land in ``vlog_profile_sessions_total{outcome}``.

A stopped session also gets a ``summary.json`` beside its artifact:
:func:`summarize` reduces the ``.xplane.pb`` to device seconds by the
program's own ``jax.named_scope`` names (``asr.*``, ``ladder.*``) and
device idle gaps by the program's own spans (``obs/trace.py`` mirrors
every span into the capture as a ``vlog:<name>`` annotation). It is
written by the thread that stopped the session once the lock is
released: the timer's own thread, or for an explicit stop (which arrives
on the heartbeat task) a daemon thread started for it.
"""

from __future__ import annotations

import bisect
import json
import logging
import re
import sys
import threading
import time
from pathlib import Path

from vlog_tpu import config

log = logging.getLogger("vlog_tpu.profiler")

_LABEL_RE = re.compile(r"[^a-zA-Z0-9_.-]+")

GAP_NS = 50_000             # shorter device gaps are launch spacing
EDGE_NS = 1_000_000         # a program run this close to an end may be cut
TOP_PROGRAMS = 8
ANNOTATION = "vlog:"
# the root span of a model engine's cycle: the line (thread) that holds
# one is the engine's (a capture names every Python thread by the
# process, "python3", not by the thread's own name)
ENGINE_ROOTS = frozenset({"lm.step", "asr.tick"})
# the program's named scopes in an op's framework name, outermost first:
# "jit(f)/jit(main)/asr.decoder_step/asr.decoder_step.mlp/dot_general",
# under a transform "jit(f)/while/body/vmap(ladder.mc)/gather"
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_.])((?:asr|ladder|lm)\.[A-Za-z0-9_.]+)")
# A TPU capture names a device-op event by its HLO instruction
# ("%fusion.48 = (f32[1,200]...) fusion(...)") and carries no framework
# name on the event (its stats are device_offset_ps, device_duration_ps
# and a time scale; my chip run, PR 26). The framework names are in the
# HLO protos the capture keeps per program in its "/host:metadata" plane:
# instruction -> op_name.
_HLO_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)[^=]*\{\s*$")
_HLO_INSTRUCTION_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
_HLO_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")


def profile_root() -> Path:
    """The artifact root (``VLOG_PROFILE_DIR`` or BASE_DIR/profiles)."""
    if config.PROFILE_DIR:
        return Path(config.PROFILE_DIR)
    return Path(config.BASE_DIR) / "profiles"


def scope_of(framework_name: str) -> str | None:
    """``asr.decoder_step.mlp`` out of ``jit(f)/.../asr.decoder_step/
    asr.decoder_step.mlp/dot_general``, ``ladder.mc`` out of
    ``.../vmap(ladder.mc)/gather``: the last (innermost) ``asr.*`` /
    ``ladder.*`` scope in the name, or None."""
    found = _SCOPE_RE.findall(framework_name)
    return found[-1] if found else None


def _fields(buf: memoryview):
    """``(field number, value)`` of one protobuf message's top level; a
    length-delimited value comes as a memoryview. Enough of the wire
    format to find the HLO protos of a capture, which
    ``jax.profiler.ProfileData`` does not hand out."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        value = shift = 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                return value

    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            yield key >> 3, varint()
        elif kind == 2:
            size = varint()
            yield key >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            yield key >> 3, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {kind}")


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction: scope}`` of one optimized HLO module's text: each
    instruction's own ``op_name``; a fusion without one takes its fused
    computation's root's, else the scope most of its instructions
    have."""
    own: dict[str, str | None] = {}
    calls: dict[str, str] = {}
    members: dict[str, list[str]] = {}
    roots: dict[str, str] = {}
    computation = ""
    for line in hlo_text.splitlines():
        found = _HLO_INSTRUCTION_RE.match(line)
        if found is None:
            header = _HLO_COMPUTATION_RE.match(line)
            if header is not None:
                computation = header.group(1)
            continue
        name = found.group(2)
        op_name = _HLO_OP_NAME_RE.search(line)
        own[name] = scope_of(op_name.group(1)) if op_name else None
        members.setdefault(computation, []).append(name)
        if found.group(1):
            roots[computation] = name
        called = _HLO_CALLS_RE.search(line)
        if called is not None:
            calls[name] = called.group(1)
    out = {}
    for name, scope in own.items():
        if scope is None and name in calls:
            inside = calls[name]
            scope = own.get(roots.get(inside, ""))
            if scope is None:
                held = [own[m] for m in members.get(inside, ()) if own[m]]
                scope = max(set(held), key=held.count) if held else None
        if scope is not None:
            out[name] = scope
    return out


def _program_scopes(raw: bytes) -> dict[str, dict[str, str]]:
    """``{program, as the "XLA Modules" line names it: {instruction:
    scope}}`` from the HLO protos in a capture's ``/host:metadata``
    plane; empty where the capture was taken without them."""
    from jax._src.lib import xla_client

    out: dict[str, dict[str, str]] = {}
    for num, plane in _fields(memoryview(raw)):
        if num != 1:                            # XSpace.planes
            continue
        parts = list(_fields(plane))
        if not any(n == 2 and bytes(v) == b"/host:metadata"
                   for n, v in parts):          # XPlane.name
            continue
        for n, entry in parts:
            if n != 4:                          # XPlane.event_metadata
                continue
            meta = dict(_fields(entry)).get(2)  # map value: XEventMetadata
            if meta is None:
                continue
            program = ""
            for n2, v in _fields(meta):
                if n2 == 2:                     # XEventMetadata.name
                    program = bytes(v).decode()
                elif n2 == 5:                   # .stats -> XStat.bytes_value
                    proto = dict(_fields(v)).get(6)
                    if proto is None:
                        continue
                    module = dict(_fields(proto)).get(1)  # HloProto.hlo_module
                    text = xla_client._xla.HloModule \
                        .from_serialized_hlo_module_proto(
                            bytes(module)).to_string()
                    out[program] = hlo_scopes(text)
    return out


def _leaves(events: list[tuple]) -> list[tuple]:
    """Drop every event that contains the next one (sorted by start,
    longest first at equal starts): an op that contains others (a
    ``while`` around a scan's steps) is not counted on top of them."""
    events.sort(key=lambda e: (e[0], -(e[1] - e[0])))
    return [ev for i, ev in enumerate(events)
            if not (i + 1 < len(events) and events[i + 1][0] < ev[1]
                    and events[i + 1][1] <= ev[1])]


def summarize(xplane_path: str | Path) -> dict:
    """Reduce a capture to what the program's own names say:

    - ``window_s``: first to last instant of device ops and ``vlog:``
      annotations; ``busy_s``: seconds in which a leaf device op ran,
      averaged over the device planes;
    - ``by_scope``: leaf device-op seconds grouped by the innermost
      ``asr.*`` / ``ladder.*`` named scope of the op's framework name
      (:func:`scope_of`), else ``unscoped``. The name comes from the HLO
      protos the capture holds (taken with ``enable_hlo_proto``, the
      profiler's default; without them every op is ``unscoped``). A
      fusion carries one name, so its seconds go to the scope of the op
      XLA named it after;
    - ``idle_by_span``: device gaps over 50 us summed under the ``vlog:``
      annotation that covers their middle (:func:`idle_owner`: a
      collection's ``gc.gen<N>`` first, then the innermost span of a
      model engine's thread, then the innermost of any thread), else
      ``no_program_span``;
    - ``programs``: for the eight compiled programs ("XLA Modules"
      line) that took most device time, their runs that lie wholly
      inside the capture, those runs' seconds and the same by-scope
      split over them alone, so that ``by_scope[s] / runs`` is scope
      ``s``'s seconds in ONE run of the program however the capture
      cut the runs at its ends.

    The arithmetic is ``benchmark/harness/trace.py``'s, kept apart from
    it: the yardstick does not import the program, nor the program it.
    Reads the file with nothing but jax; :func:`summarize_planes` is the
    reduction of what it read."""
    return summarize_planes(_load_planes(xplane_path))


def _load_planes(xplane_path: str | Path) -> list[dict]:
    """A capture as plain planes, ``[{"name", "lines": [{"name",
    "events": [(start_ns, end_ns, name)]}]}]``: a device plane's "XLA
    Ops" events named by their scope (the program that ran them, from
    its "XLA Modules" line, and its HLO protos) and its "XLA Modules"
    events; a host plane's lines with their ``vlog:`` annotations alone,
    the prefix dropped."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(xplane_path))
    by_program = _program_scopes(Path(xplane_path).read_bytes())
    planes = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            runs = sorted(
                (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
                 ev.name) for ev in (lines["XLA Modules"].events
                                     if "XLA Modules" in lines else ()))
            starts = [r[0] for r in runs]
            events = []
            scopes: dict[tuple, str] = {}   # one lookup per (program, op)
            for ev in lines["XLA Ops"].events:
                start = int(ev.start_ns)
                i = bisect.bisect_right(starts, start) - 1
                program = runs[i][2] if i >= 0 and start < runs[i][1] \
                    else ""
                scope = scopes.get((program, ev.name))
                if scope is None:
                    instruction = ev.name.split(" = ", 1)[0].strip() \
                        .lstrip("%")
                    scope = scopes[(program, ev.name)] = by_program.get(
                        program, {}).get(instruction, "unscoped")
                events.append((start, start + int(ev.duration_ns), scope))
            planes.append({"name": plane.name, "lines": [
                {"name": "XLA Ops", "events": events},
                {"name": "XLA Modules", "events": runs}]})
        elif plane.name.startswith("/host:"):
            planes.append({"name": plane.name, "lines": [
                {"name": line.name, "events": [
                    (int(ev.start_ns),
                     int(ev.start_ns) + int(ev.duration_ns),
                     ev.name[len(ANNOTATION):])
                    for ev in line.events if ev.name.startswith(ANNOTATION)]}
                for line in plane.lines]})
    return planes


def idle_owner(at: int, spans: list[tuple[int, int, str, bool]]) -> str:
    """The span a device gap whose middle is ``at`` is booked to.
    ``spans``: ``(start_ns, end_ns, name, of_an_engine_thread)`` sorted by
    start. A collection (``gc.gen<N>``) stops every thread, so it wins
    wherever it covers ``at``; else the innermost span of a model
    engine's thread, which is what the device waits for, over a shorter
    span of a bystander thread; else the innermost of any thread."""
    best: dict[str, tuple[int, str]] = {}
    for s, e, name, engine in spans:
        if s > at:
            break
        if e < at:
            continue
        for kind, hit in (("gc", name.startswith("gc.")), ("engine", engine),
                          ("any", True)):
            if hit and (kind not in best or e - s < best[kind][0]):
                best[kind] = (e - s, name)
    for kind in ("gc", "engine", "any"):
        if kind in best:
            return best[kind][1]
    return "no_program_span"


def summarize_planes(planes: list[dict]) -> dict:
    """:func:`summarize` of planes as :func:`_load_planes` returns them
    (device op events already named by scope)."""
    device_lines: list[list[tuple]] = []    # leaf candidates per device
    module_lines: list[list[tuple]] = []    # program runs, beside them
    spans: list[tuple[int, int, str, bool]] = []
    for plane in planes:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        if plane["name"].startswith("/device:"):
            if not lines.get("XLA Ops"):
                continue
            device_lines.append(list(lines["XLA Ops"]))
            module_lines.append([(s, e, name.split("(", 1)[0])
                                 for s, e, name in lines.get(
                                     "XLA Modules", ())])
        elif plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                engine = any(name in ENGINE_ROOTS
                             for _s, _e, name in line["events"])
                spans.extend((s, e, name, engine)
                             for s, e, name in line["events"])
    spans.sort()
    edges = [t for events in device_lines for s, e, _ in events
             for t in (s, e)] + [t for s, e, _, _ in spans for t in (s, e)]
    lo, hi = (min(edges), max(edges)) if edges else (0, 0)

    busy_ns = 0
    scope_ns: dict[str, int] = {}
    gap_ns: dict[str, int] = {}

    def gap(a: int, b: int) -> None:
        if b - a > GAP_NS:
            name = idle_owner((a + b) // 2, spans)
            gap_ns[name] = gap_ns.get(name, 0) + (b - a)

    programs: dict[str, dict] = {}
    for events, runs in zip(device_lines, module_lines):
        whole = [r for r in runs
                 if r[0] - lo >= EDGE_NS and hi - r[1] >= EDGE_NS]
        starts = [r[0] for r in whole]
        for s, e, name in whole:
            prog = programs.setdefault(
                name, {"runs": 0, "ns": 0, "scope_ns": {}})
            prog["runs"] += 1
            prog["ns"] += e - s
        prev_end = lo
        for s, e, scope in _leaves(events):
            busy_ns += e - s
            scope_ns[scope] = scope_ns.get(scope, 0) + (e - s)
            gap(prev_end, s)
            prev_end = max(prev_end, e)
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < whole[i][1]:
                into = programs[whole[i][2]]["scope_ns"]
                into[scope] = into.get(scope, 0) + (e - s)
        gap(prev_end, hi)
    n = max(len(device_lines), 1)

    def seconds(d: dict[str, int], over: int = n) -> dict[str, float]:
        return {k: v / over / 1e9 for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])}

    top = sorted(programs.items(), key=lambda kv: -kv[1]["ns"])
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / n / 1e9,
            "devices_traced": len(device_lines),
            "by_scope": seconds(scope_ns), "idle_by_span": seconds(gap_ns),
            "programs": {name: {"runs": p["runs"], "seconds": p["ns"] / 1e9,
                                "by_scope": seconds(p["scope_ns"], 1)}
                         for name, p in top[:TOP_PROGRAMS]}}


def _bump(outcome: str) -> None:
    try:
        from vlog_tpu.obs.metrics import runtime

        runtime().profile_sessions.labels(outcome).inc()
    except Exception:   # noqa: BLE001 — metrics are best-effort
        pass


class DeviceProfiler:
    """One process's profiling sessions (singleton via :func:`profiler`)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()             # lock-order: 39
        self._active_dir: str | None = None       # guarded-by: _lock
        self._started_at = 0.0                    # guarded-by: _lock
        self._duration_s = 0.0                    # guarded-by: _lock
        self._timer: threading.Timer | None = None  # guarded-by: _lock
        self._summary_thread: threading.Thread | None = None  # guarded-by: _lock

    # ---- session lifecycle -------------------------------------------

    def start(self, duration_s: float | None = None,
              label: str = "") -> dict:
        """Start one bounded trace session; returns the session info or
        an ``{"error": ...}`` dict (command-channel style, never raises
        into the heartbeat task)."""
        if "jax" not in sys.modules:
            _bump("rejected")
            return {"error": "jax is not initialized in this process; "
                             "nothing to profile (run a job first)"}
        try:
            dur = float(duration_s) if duration_s else 10.0
        except (TypeError, ValueError):
            dur = 10.0
        dur = max(1.0, min(dur, config.PROFILE_MAX_S))
        root = profile_root().resolve()
        stamp = time.strftime("%Y%m%d-%H%M%S", time.gmtime())
        name = f"{stamp}-{_LABEL_RE.sub('_', label)[:48]}" if label \
            else stamp
        target = (root / name).resolve()
        if root not in target.parents and target != root:
            _bump("rejected")
            return {"error": "profile label escapes the artifact root"}
        with self._lock:
            if self._active_dir is not None:
                _bump("rejected")
                return {"error": "a profiling session is already active",
                        "active": self._status_locked()}
            target.mkdir(parents=True, exist_ok=True)
            try:
                import jax

                jax.profiler.start_trace(str(target))
            except Exception as exc:   # noqa: BLE001 — surface, don't die
                _bump("error")
                log.warning("profiler start failed", exc_info=True)
                return {"error": f"profiler start failed: {exc}"}
            self._active_dir = str(target)
            self._started_at = started = time.time()
            self._duration_s = dur
            self._timer = threading.Timer(dur, self._timed_stop)
            self._timer.daemon = True
            self._timer.name = "vlog-profiler-stop"
            self._timer.start()
        _bump("started")
        log.info("profiling session started: %s (%.1fs)", target, dur)
        return {"profiling": True, "dir": str(target),
                "duration_s": dur, "started_at": started}

    def stop(self) -> dict:
        """Stop the active session early (idempotent). The summary is
        left to a thread of its own: this call arrives on the heartbeat
        task. The returned ``summary`` is where it will be."""
        with self._lock:
            out = self._stop_locked(source="explicit")
        if "summary" in out:
            writer = threading.Thread(
                target=_write_summary, args=(out["dir"],),
                name="vlog-profiler-summary", daemon=True)
            with self._lock:
                self._summary_thread = writer
            writer.start()
        return out

    def _timed_stop(self) -> None:
        with self._lock:
            out = self._stop_locked(source="timer")
        if "summary" in out:
            _write_summary(out["dir"])

    def wait_summary(self, timeout: float | None = None) -> None:
        """Join the summary writer of the last explicit stop (tests)."""
        with self._lock:
            writer = self._summary_thread
        if writer is not None:
            writer.join(timeout)

    def _stop_locked(self, source: str) -> dict:
        if self._active_dir is None:
            return {"profiling": False, "error": "no active session"}
        active, started = self._active_dir, self._started_at
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        self._active_dir = None
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:   # noqa: BLE001 — a dead runtime still clears
            _bump("error")
            log.warning("profiler stop (%s) failed", source, exc_info=True)
            return {"profiling": False, "dir": active,
                    "error": "profiler stop failed (session cleared)"}
        _bump("completed")
        log.info("profiling session stopped (%s): %s", source, active)
        return {"profiling": False, "dir": active,
                "summary": str(Path(active) / "summary.json"),
                "elapsed_s": round(time.time() - started, 2)}

    # ---- status ------------------------------------------------------

    def _status_locked(self) -> dict:
        if self._active_dir is None:
            return {"profiling": False}
        return {"profiling": True, "dir": self._active_dir,
                "started_at": self._started_at,
                "duration_s": self._duration_s,
                "remaining_s": round(max(
                    0.0, self._started_at + self._duration_s
                    - time.time()), 2)}

    def status(self) -> dict:
        with self._lock:
            info = self._status_locked()
        info["root"] = str(profile_root())
        info["sessions"] = self.list_sessions()
        return info

    def list_sessions(self) -> list[str]:
        """Artifact directories currently on disk (newest first)."""
        root = profile_root()
        if not root.is_dir():
            return []
        return sorted((p.name for p in root.iterdir() if p.is_dir()),
                      reverse=True)[:32]


def _write_summary(session_dir: str) -> None:
    """``summary.json`` beside a stopped session's artifact; a capture
    that cannot be read leaves a log line, never an exception."""
    try:
        found = sorted(Path(session_dir).glob(
            "plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError("the session wrote no .xplane.pb")
        summary = summarize(found[-1])
        tmp = Path(session_dir) / "summary.json.tmp"
        tmp.write_text(json.dumps(summary, indent=1))
        tmp.rename(Path(session_dir) / "summary.json")
    except Exception:   # noqa: BLE001 — the artifact itself is intact
        log.warning("profile summary failed: %s", session_dir,
                    exc_info=True)


_profiler: DeviceProfiler | None = None
_profiler_lock = threading.Lock()


def profiler() -> DeviceProfiler:
    """The process-wide profiler (lazy singleton, runtime() idiom)."""
    global _profiler
    if _profiler is None:
        with _profiler_lock:
            if _profiler is None:
                _profiler = DeviceProfiler()
    return _profiler


def reset_profiler() -> None:
    """Test hook: stop any active session and drop the singleton."""
    global _profiler
    with _profiler_lock:
        if _profiler is not None:
            _profiler.stop()
            _profiler.wait_summary(30.0)
        _profiler = None
