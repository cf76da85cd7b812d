"""Continuous-batching ASR engine: one shared Whisper serving every job.

Pre-engine, each transcription job reloaded weights from disk, decoded
its own windows sequentially, and grabbed a full-device ``make_mesh()``
that ignored the mesh scheduler's slot leases. The engine replaces that
with the WhisperPipe/WhisperFlow serving shape (PAPERS.md): a per-process
singleton owns the Whisper assets (loaded once via the memoized
``load_whisper``) and a cross-job :class:`~vlog_tpu.asr.queue.WindowQueue`;
a tick thread packs windows from many concurrent jobs into fixed-shape
bucketed batches and runs one batched mel -> encode -> beam-decode
forward per tick. Freed batch rows backfill from the queue as jobs' tails
drain — the continuous-batching core.

Determinism contract (the packing-invariance guarantee): a job's cues are
a pure function of its own windows. Every forward runs at one of a fixed
set of bucket shapes, zero-padded rows fill the remainder, and the
Whisper forward has no cross-window ops (per-row conv, per-position
layernorm, attention within a window: under beam search the K beam
rows of ONE window attend over that window's K cache slots through an
ancestry mask, asr/decode.py) — so window i's tokens do not depend on
windows j != i. Verified empirically across bucket sizes and mesh sharding
before this design was locked in; ``tests/test_asr_engine.py`` asserts
byte-identical ``captions.vtt`` solo vs. packed with N other jobs.

Mesh integration: the ENGINE owns the slot demand, not the jobs — N
concurrent transcriptions share one ``MeshScheduler`` ticket
(``parallel/engine_host.py::HeldLease``), acquired when the queue has
work and released at tick boundaries when the queue drains or other
demand is pending (work-conserving: a lone engine gets the full-mesh
fallback lease, and gives it back as soon as a transcode job queues
up). Which engine is resident in the process, and who builds the next
one, is ``parallel/engine_host.py::HOST``'s to say; ``get_engine`` below
only knows how to load this plane's assets.

The engine traces itself. The tick thread runs under a trace context of
its own (one trace id per engine, a ``TraceBuffer`` as the sink) and
opens one span tree per tick: ``asr.tick`` (attrs ``seq``, ``n``,
``rows``, ``key``) with the children ``asr.tick.coalesce`` (the wait for
a first window, timed-out waits since the last tick included, and the
coalescing sleep), ``asr.tick.lease``,
``asr.tick.take``, ``asr.tick.stack``, ``asr.tick.mel``,
``asr.tick.generate`` (whose children ``asr.generate.dispatch`` and
``asr.generate.device_wait`` are opened inside
``decode.generate_batch``), ``asr.tick.parse`` and ``asr.tick.deliver``.
At the end of a tick the buffer is drained into the tick's ``batch_log``
entry, which is **the tick record**: beside ``rows``, ``n``,
``occupancy``, ``jobs`` and ``elapsed_s`` (stack to the token pull, so
it ends before the parse) it holds ``seq``, ``t_start``, ``t_dispatch``
(the jitted call returned: the device has the program), ``t_ready`` (the
tokens are on the host), ``t_end``, ``phase_s`` (seconds per phase, from
the spans' own durations), ``gap_s`` (``t_dispatch`` minus the previous
tick's ``t_ready``; ``None`` on the first tick, after an idle wait and
after a failed batch), ``windows``, ``wait_s``, ``build_s`` and
``first_of_shape`` (``parallel/compile_cache.py::build_seconds``), all
on ``time.monotonic()``. The entry is appended before the results are
delivered; ``phase_s["deliver"]`` and ``t_end`` are filled in place
afterwards. Who kept the chip waiting (``obs/hostwait.py``) is on the
same record: ``wait`` is the record of the token pull (``polls``,
``gap_max_s``, ``cpu_s``, ``gc_s``, ``wait_s``, ``ready_max_s``,
``copy_s``; ``None`` for a stand-in
of ``generate_batch``), ``gc_s`` the process's collection seconds
between ``t_start`` and ``t_end``, ``stall`` the cause of a stalled pull
(``gc``, ``host``, ``runtime``, judged against the recent pulls of the
same rows) or ``None``; the tick is not pipelined, so its idle gap is
``gap_s``. ``stats()`` ``waits`` keeps the stalls by cause and the five
longest pulls, and survives ``close()``. Every span is also a
``vlog:<name>`` annotation in a
profiler capture (``obs/trace.py``), so device idle gaps can be named by
phase (``obs/profiler.py::summarize``). Jobs keep their own spans: the
daemon wraps an attempt in ``worker.transcribe`` and
``worker/transcribe.py`` adds ``asr.job.*`` beneath it.
"""

from __future__ import annotations

import queue as stdqueue
import threading
import time

import numpy as np

from vlog_tpu import config
from vlog_tpu.asr import mel as melmod
from vlog_tpu.asr.load import WhisperAssets, load_whisper
from vlog_tpu.asr.queue import BatchKey, WindowQueue, WorkItem
from vlog_tpu.asr.vtt import Cue
from vlog_tpu.obs import hostwait, trace
from vlog_tpu.parallel import compile_cache
from vlog_tpu.parallel.engine_host import HOST, HeldLease
from vlog_tpu.utils import failpoints

# phases of a tick record, in the order a cycle runs them; the last part
# of the span that reports each (asr.tick.<phase>, asr.generate.<phase>)
PHASES = ("coalesce", "lease", "take", "stack", "mel", "dispatch",
          "device_wait", "parse", "deliver")

# (model config, rows, mesh width, BatchKey) shapes that have run in this
# process: jit caches are per process, so "first of its shape" is too
_SHAPES_SEEN: set[tuple] = set()
_SHAPES_LOCK = threading.Lock()


class AsrJobError(RuntimeError):
    """A batch containing this job's windows failed to decode."""


class JobHandle:
    """One transcription job's membership in the engine.

    ``submit`` windows (compute thread), then iterate :meth:`results`
    until every submitted window has come back. Results arrive in batch
    completion order, not index order — callers slot them by index.
    """

    def __init__(self, engine: "AsrEngine", job: str, key: BatchKey):
        self.job = job
        self.key = key
        self._engine = engine
        self._results: stdqueue.Queue = stdqueue.Queue()
        self._cancelled = threading.Event()
        self.submitted = 0
        self.delivered = 0

    def submit(self, index: int, start_s: float,
               samples: np.ndarray) -> None:
        """Enqueue one VAD-live window (blocks under queue backpressure)."""
        failpoints.hit("asr.submit")
        if self._cancelled.is_set():
            raise AsrJobError(f"job {self.job} is cancelled")
        self._engine._queue.put(
            self.key,
            WorkItem(job=self.job, index=index, start_s=start_s,
                     samples=samples),
            cancel=self._cancelled)
        self.submitted += 1

    def results(self):
        """Yield ``(index, cues, queue_wait_s)`` per submitted window.

        Raises :class:`AsrJobError` if a batch carrying this job's
        windows failed (the engine itself survives and keeps serving
        other jobs)."""
        while self.delivered < self.submitted:
            kind, payload = self._results.get()
            if kind == "error":
                raise AsrJobError(str(payload)) from (
                    payload if isinstance(payload, BaseException) else None)
            self.delivered += 1
            yield payload

    def drain_ready(self):
        """Non-blocking: yield results already delivered by the engine —
        the drain path's in-flight-batch flush (windows decoded between
        the preemption notice and the abort still reach the checkpoint)."""
        while self.delivered < self.submitted:
            try:
                kind, payload = self._results.get_nowait()
            except stdqueue.Empty:
                return
            if kind == "error":
                return
            self.delivered += 1
            yield payload

    def cancel(self) -> None:
        """Drop this job's queued windows and wake any blocked waiter."""
        self._cancelled.set()
        self._engine._queue.cancel_job(self.job)
        self._results.put(("error", f"job {self.job} cancelled"))

    def close(self) -> None:
        """Unregister from the engine (always call; idempotent)."""
        self._cancelled.set()
        self._engine._queue.cancel_job(self.job)
        self._engine._drop(self.job)

    # engine-side delivery -------------------------------------------------
    def _deliver(self, index: int, cues: list[Cue], wait_s: float) -> None:
        self._results.put(("ok", (index, cues, wait_s)))

    def _fail(self, exc: BaseException) -> None:
        self._results.put(("error", exc))


class AsrEngine:
    """Per-process continuous-batching Whisper server (see module doc)."""

    def __init__(self, assets: WhisperAssets, *, scheduler=None,
                 batch_windows: int | None = None,
                 tick_s: float | None = None,
                 queue_max: int | None = None,
                 window_s: float | None = None):
        self.assets = assets
        self.scheduler = scheduler
        self.batch_windows = batch_windows or config.ASR_BATCH_WINDOWS
        self.tick_s = config.ASR_TICK_S if tick_s is None else tick_s
        self.window_s = window_s or config.WHISPER_CHUNK_S
        self._queue = WindowQueue(queue_max or config.ASR_QUEUE_MAX)
        self._lock = threading.Lock()             # lock-order: 20
        self._jobs: dict[str, JobHandle] = {}   # guarded-by: _lock
        self._started = False                   # guarded-by: _lock
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._hold = HeldLease(scheduler)
        # One tick record per tick (module docstring): batch composition
        # for tests/stats, timing for whoever asks where a tick went.
        self.batch_log: list[dict] = []         # guarded-by: _lock
        self.windows_decoded = 0                # guarded-by: _lock
        self.waits = hostwait.WaitBook()        # guarded-by: _lock
        self._trace = trace.TraceContext(trace.new_id(), None,
                                         trace.TraceBuffer())
        # tick thread only
        self._seq = 0
        self._prev_ready: float | None = None
        # phase seconds of passes that served nothing (a timed-out wait,
        # a failed batch) since the last record: the next tick's
        self._carry_s = dict.fromkeys(PHASES, 0.0)

    # job lifecycle --------------------------------------------------------

    def begin_job(self, job: str, *, language: str,
                  task: str = "transcribe", max_new: int | None = None,
                  beam: int = 1) -> JobHandle:
        """Register a job; windows co-batch only with jobs sharing the
        same (language, task, max_new, beam) — ``generate_batch`` builds
        one shared prompt per batch."""
        key = BatchKey(language=language, task=task, max_new=max_new,
                       beam=beam)
        handle = JobHandle(self, job, key)
        with self._lock:
            self._jobs[job] = handle
            if not self._started:
                self._started = True
                hostwait.GC.install()       # once a process
                self._thread = trace.start_thread(
                    self._trace, self._run, name="vlog-asr-engine")
        return handle

    def detect_language(self, samples: np.ndarray) -> str:
        """Language-id on one window (the job's own first live window, so
        co-batched jobs can never pollute the vote)."""
        from vlog_tpu.asr.decode import detect_language

        batch = melmod.pad_or_trim(samples.astype(np.float32))[None, :]
        feats = melmod.log_mel_spectrogram(
            batch, n_mels=self.assets.cfg.num_mel_bins)
        return detect_language(self.assets, feats)

    def active(self) -> bool:
        """Is the engine currently serving (queued work or lease held)?
        The daemon uses this to keep claiming transcription jobs that
        will pile onto the running engine even when mesh capacity reads
        zero."""
        return self._hold.held.is_set() or self._queue.pending() > 0

    def stats(self) -> dict:
        from vlog_tpu.asr.decode import kv_pool

        with self._lock:
            batches = len(self.batch_log)
            occ = (sum(b["occupancy"] for b in self.batch_log) / batches
                   if batches else 0.0)
            return {"batches": batches, "windows": self.windows_decoded,
                    "mean_occupancy": occ,
                    "pending": self._queue.pending(),
                    "kv_pool": kv_pool.stats(),
                    "waits": self.waits.stats()}

    def close(self) -> None:
        from vlog_tpu.asr.decode import kv_pool

        self._stop.set()
        self._queue.close()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=30)
        kv_pool.reset()     # the cache pages are this plane's device memory

    def _drop(self, job: str) -> None:
        with self._lock:
            self._jobs.pop(job, None)

    # tick loop ------------------------------------------------------------

    def _run(self) -> None:
        compile_cache.thread_built_s()  # the meter is on before a build
        try:
            while not self._stop.is_set():
                self._cycle()
        finally:
            self._hold.release()
            self._trace.buffer.drain()

    def _renegotiate(self) -> None:
        """Work-conserving renegotiation at the tick boundary: any lease
        goes back when the window queue drains; a full-mesh fallback
        lease shrinks to a slot as soon as other demand queues."""
        if self._queue.pending() == 0:
            self._hold.release()
        else:
            self._hold.yield_full_mesh()

    def _cycle(self) -> None:
        """One pass of the tick loop under one ``asr.tick`` span; a pass
        that served windows leaves a tick record."""
        entry = None
        built0 = compile_cache.thread_built_s()
        with trace.span("asr.tick") as tick:
            with trace.span("asr.tick.coalesce"):
                idle = not self._queue.wait_for_work(timeout=0.2)
                if not idle and self.tick_s > 0:
                    # Coalesce: let concurrent jobs land windows before
                    # packing, so the first tick is not a batch of one.
                    time.sleep(self.tick_s)
            if idle:
                self._hold.release()    # give the slot back
            else:
                with trace.span("asr.tick.lease"):
                    leased = self._hold.acquire(self._stop)
                items: list[WorkItem] = []
                if leased:
                    with trace.span("asr.tick.take"):
                        key = self._queue.pick_key()
                        if key is not None:
                            items = self._queue.take(key,
                                                     self.batch_windows)
                if items:
                    entry = self._tick(tick, key, items, built0)
                with trace.span("asr.tick.lease"):
                    self._renegotiate()
        spans = self._trace.buffer.drain()
        if entry is None:
            # idle or failed: the thread's seconds go to the next tick's
            # phases (its wait for a first window began here), but the
            # device's time since the last tick is not that tick's gap
            trace.add_phase_seconds(self._carry_s, spans)
            self._prev_ready = None
            return
        with self._lock:
            self._fold(entry, tick, spans)
        self._carry_s = dict.fromkeys(PHASES, 0.0)
        self._prev_ready = entry["t_ready"]

    def _bucket_rows(self, n: int, width: int) -> int:
        """Smallest power-of-two bucket >= n (recompile-free: every batch
        runs at one of a handful of shapes), rounded up to a multiple of
        the mesh width so rows shard evenly."""
        rows = 1
        while rows < n:
            rows *= 2
        if width > 1:
            rows += (-rows) % width
        return rows

    def _tick(self, tick: trace.Span, key: BatchKey, items: list[WorkItem],
              built0: float) -> dict | None:
        """Decode one batch; returns its tick record (already in
        ``batch_log``, its results delivered) or None if it failed."""
        lease = self._hold.lease
        n = len(items)
        try:
            with trace.span("asr.tick.stack") as stacking:
                failpoints.hit("asr.batch")
                mesh = None
                width = 1
                if lease is not None and lease.width > 1:
                    from vlog_tpu.parallel.mesh import make_mesh

                    mesh = make_mesh("data:-1", devices=list(lease.devices))
                    width = lease.width
                elif lease is None and self.scheduler is None:
                    # No scheduler anywhere (CLI, quality_bench): the
                    # classic ad-hoc full-device mesh.
                    import jax

                    if len(jax.devices()) > 1:
                        from vlog_tpu.parallel.mesh import make_mesh

                        mesh = make_mesh()
                        width = mesh.devices.size
                rows = self._bucket_rows(n, width)
                stack = [melmod.pad_or_trim(it.samples.astype(np.float32))
                         for it in items]
                stack += [np.zeros_like(stack[0])] * (rows - n)
                batch = np.stack(stack)
            shape = (self.assets.cfg, rows, width, key)
            with _SHAPES_LOCK:
                first_of_shape = shape not in _SHAPES_SEEN
                _SHAPES_SEEN.add(shape)
            tick.attrs.update(seq=self._seq, n=n, rows=rows, key="/".join(
                str(part) for part in key))
            with trace.span("asr.tick.mel"):
                feats = melmod.log_mel_spectrogram(
                    batch, n_mels=self.assets.cfg.num_mel_bins)
                if mesh is not None:
                    from vlog_tpu.parallel.mesh import shard_frames

                    (feats,) = shard_frames(mesh, feats)
            # through the module at call time: whoever wraps
            # decode.generate_batch after the engine was built is called
            from vlog_tpu.asr import decode

            with trace.span("asr.tick.generate") as generating:
                toks, no_speech = decode.generate_batch(
                    self.assets, feats, language=key.language,
                    task=key.task, max_new=key.max_new, beam=key.beam)
            toks, no_speech = toks[:n], no_speech[:n]
            st = self.assets.tokens
            tokenizer = self.assets.tokenizer
            t0 = stacking.started_mono
            elapsed = generating.ended_mono - t0
            with trace.span("asr.tick.parse"):
                results = []
                for row, nsp, it in zip(toks, no_speech, items):
                    cues: list[Cue] = []
                    if st.no_speech is None or nsp <= 0.6:
                        for seg in decode.parse_segments(
                                row, st, window_s=self.window_s):
                            text = tokenizer.decode(
                                [t for t in seg.token_ids if t < st.sot])
                            cues.append(Cue(it.start_s + seg.start_s,
                                            it.start_s + seg.end_s, text))
                    results.append((it, cues, t0 - it.enqueued_at))
        except Exception as exc:  # noqa: BLE001 — the engine must survive
            # one bad batch; the affected jobs' attempts fail through the
            # normal job-failure handling and the tick loop keeps serving.
            self._fail_items(items, exc)
            self._observe_batch_metrics(key, items, rows=0, elapsed=0.0,
                                        device_wait=0.0, stall=None,
                                        failed=True)
            return None
        entry = {
            "rows": rows, "n": n, "occupancy": n / rows,
            "jobs": [it.job for it in items], "elapsed_s": elapsed,
            "seq": self._seq,
            "windows": [(it.job, it.index) for it in items],
            "wait_s": [wait_s for _it, _cues, wait_s in results],
            "first_of_shape": first_of_shape,
            "build_s": compile_cache.thread_built_s() - built0,
        }
        self._seq += 1
        with self._lock:
            self._fold(entry, tick, self._trace.buffer.snapshot())
            entry["stall"] = None if entry["wait"] is None else \
                self.waits.add(entry["wait"], key=rows, seq=entry["seq"])
            self.windows_decoded += n
            self.batch_log.append(entry)
            handles = {it.job: self._jobs.get(it.job) for it in items}
        with trace.span("asr.tick.deliver"):
            for it, cues, wait_s in results:
                h = handles.get(it.job)
                if h is not None and not h._cancelled.is_set():
                    h._deliver(it.index, cues, wait_s)
            self._observe_batch_metrics(
                key, items, rows=rows, elapsed=elapsed,
                device_wait=entry["phase_s"]["device_wait"],
                stall=entry["stall"], failed=False)
        return entry

    def _fold(self, entry: dict, tick: trace.Span,
              spans: list[trace.Span]) -> None:
        """The tick's spans closed so far into its record: seconds per
        phase from the spans' own durations, the instants from their
        ends. Called under ``_lock`` when the entry is appended (before
        delivery) and again when the tick has closed."""
        phase_s = trace.add_phase_seconds(dict(self._carry_s), spans)
        generating = dispatched = ready = wait = None
        for sp in spans:
            if sp.name == "asr.tick.generate":
                generating = sp
            elif sp.name == "asr.generate.dispatch":
                dispatched = sp.ended_mono
            elif sp.name == "asr.generate.device_wait":
                ready = sp.ended_mono
                wait = sp.attrs.get("wait")
        # a stand-in for decode.generate_batch opens no spans of its own
        entry["t_start"] = tick.started_mono
        entry["t_dispatch"] = (dispatched if dispatched is not None
                               else generating.started_mono)
        entry["t_ready"] = (ready if ready is not None
                            else generating.ended_mono)
        entry["t_end"] = tick.ended_mono
        entry["wait"] = wait
        entry["gc_s"] = None if tick.ended_mono is None else \
            hostwait.GC.seconds_between(tick.started_mono, tick.ended_mono)
        entry["phase_s"] = phase_s
        entry["gap_s"] = (None if self._prev_ready is None
                          else entry["t_dispatch"] - self._prev_ready)

    def _fail_items(self, items: list[WorkItem], exc: BaseException) -> None:
        with self._lock:
            handles = {it.job: self._jobs.get(it.job) for it in items}
        for job in {it.job for it in items}:
            h = handles.get(job)
            if h is not None:
                h._fail(exc)

    def _observe_batch_metrics(self, key: BatchKey, items: list[WorkItem],
                               *, rows: int, elapsed: float,
                               device_wait: float, stall: str | None,
                               failed: bool) -> None:
        try:
            from vlog_tpu.obs.metrics import runtime

            m = runtime()
            m.asr_batches.labels(
                result="error" if failed else "ok").inc()
            if failed:
                m.asr_windows.labels(result="failed").inc(len(items))
                return
            n = len(items)
            m.asr_windows.labels(result="decoded").inc(n)
            m.asr_batch_occupancy.set(n / rows if rows else 0.0)
            m.asr_pad_waste.set((rows - n) / rows if rows else 0.0)
            if elapsed > 0:
                m.asr_windows_per_second.set(n / elapsed)
            # the host's wait on the tick's device program (the token
            # pull), not the host's whole tick: stacking, mel dispatch
            # and a first shape's compile are no device seconds
            m.device_seconds.labels("asr", "forward").inc(device_wait)
            if stall is not None:
                m.engine_stalls.labels("asr", stall).inc()
            now = time.monotonic()
            for it in items:
                m.asr_queue_wait.observe(max(0.0, now - it.enqueued_at))
        except Exception:  # noqa: BLE001 — metrics never break serving
            pass


# The process's engine (parallel/engine_host.py holds it) --------------

def get_engine(model_dir: str, *, scheduler=None) -> AsrEngine:
    """The process's shared engine, (re)built when the checkpoint dir,
    quant mode, or scheduler changes (tests swap tiny model dirs; the
    daemon always passes its one scheduler singleton). One model engine
    is resident at a time and one is built at a time: the host first
    waits for another plane's engine to go idle and closes it."""
    from vlog_tpu.asr.load import resolve_quant

    quant = resolve_quant()

    def build() -> AsrEngine:
        compile_cache.ensure_compile_cache()
        return AsrEngine(load_whisper(model_dir, quant),
                         scheduler=scheduler)

    return HOST.obtain("asr", (str(model_dir), id(scheduler), quant), build)


def peek_engine() -> AsrEngine | None:
    """The process engine if one exists — never builds one."""
    return HOST.peek("asr")


def reset_engine() -> None:
    """Tear down the process engine (tests)."""
    HOST.evict("asr")
