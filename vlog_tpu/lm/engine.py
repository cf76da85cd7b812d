"""The step engine: one resident transcript model serving every digest
job, batching at the step.

A thread of its own (``vlog-lm-engine``) runs steps under a
``MeshScheduler`` lease (``parallel/engine_host.py::HeldLease``, as
``AsrEngine`` holds its own: acquired when there is work, given back
when the engine drains or other demand queues). Every step takes ALL
resident decoding rows (one token each, at most ``rows``) and at most
ONE prefill chunk (at most ``chunk`` tokens) of one waiting request; a
request's row joins the decoding rows when its last chunk is done and
leaves at its last token. Decoding is greedy and the next token goes
back in on the device (``model.py::build_step``). Shapes are bucketed (the chunk: 0, page,
2 page, ... chunk; the rows: always ``rows``) and :meth:`LmEngine.prepare`
builds and runs every one of them, so nothing compiles once requests
flow.

The host runs one step ahead of the device: step ``n`` is planned and
dispatched from what the host already knows (lengths and counts, never
token values) before step ``n - 1``'s tokens are pulled, so the device
goes from one step into the next while the host delivers. A request that
ends early (``eos_id``) is noticed at delivery and leaves at the next
plan; what the step in flight computed for it is dropped.

The engine traces itself as ``AsrEngine`` does: one span tree per step,
``lm.step`` with the children ``lm.step.admit`` (new requests in, the
lease), ``lm.step.pages`` (page tables grown, window pages that fell
behind the band given back), ``lm.step.stack`` (the plan's arrays),
``lm.step.dispatch``, ``lm.step.device_wait`` (the pull of the step
before) and ``lm.step.deliver``. The spans are folded into **the step
record** (``step_log``): ``seq``, ``t_start``, ``t_dispatch`` (the call
returned: the device has the program), ``t_ready`` (its tokens are on
the host), ``t_end`` (delivered), ``phase_s``, ``gap_s`` (``t_dispatch``
minus the end of the iteration before: the host's share of a step),
``step_s`` (``t_ready`` minus the later of the step before's ``t_ready``
and this step's ``t_dispatch``), ``decode_rows``, ``prefill_tokens``,
``row_pos`` (the rows' positions), ``chunk`` (the bucket), ``context``
(the chunk's first position), ``attn_chunk_form`` (the form the chunk's
attention took in this bucket's program, ``"kernel"`` or ``"loop"``:
``model.py::attention_form``, or for latent attention
``"latent_expanded_kernel"`` / ``"latent_expanded_loop"``; ``None`` for
a step without a chunk), ``attn_select_form`` (the form in which the
chunk chose its keys, fixed with the program: ``"kernel"`` or ``"loop"``,
``model.py::select_form``; ``None`` without a chunk or for a model with
no indexer), ``attn_rows_form`` (the rows', fixed with the
program too: ``"rows_kernel"`` or ``"loop"`` from the same function,
``"gathered"`` for a model with an indexer, ``"latent_absorbed"`` for
latent attention), ``moe_combine_form`` (the form in which the
experts' rows were summed back, fixed with the program: ``"kernel"`` or
``"xla"``, ``moe.py::combine_form``; ``None`` for a model without expert
layers), ``rows_context`` (positions
the rows' attention read a layer: each row's position plus one),
``chunk_tag``, ``emitted`` (tags of the requests that got a token),
``pages_in_use`` by class, ``pages_freed``, ``pool_wait_rows`` (rows that
stood empty in this step because the request next in line waited for
pages, not for a row: the pool, not ``rows``, bounded what was
resident), ``expert_load`` and ``window_pages`` or, for a model with an
indexer, ``sparse_keys`` (keys one layer attended, keys a causal-dense
layer would have; from the device, read out with the tokens) or, for a
model with hyper-connections, ``hc_defect`` (the largest distance of a
row or column sum of any ``H_res`` of the step from 1; from the device)
or, for a model with DeltaNet layers, ``held_choices`` (valid
token-choice pairs routed to the experts held here, all valid pairs;
from the device), ``gdn_chunk_form`` (``"chunkwise"``, ``None`` without a
chunk) and ``gdn_rows_form`` (``"recurrent"``), both ``None`` for other
models, ``state_slots`` (state slots in use), ``build_s``; all instants
on ``time.monotonic()``.

Who kept the chip waiting (``obs/hostwait.py``) is on the same record:
``wait`` is the record of the pull of the step's tokens (``polls``,
``gap_max_s``, ``cpu_s``, ``gc_s``, ``wait_s``, ``ready_max_s``,
``copy_s``), ``gc_s`` the process's
collection seconds between ``t_start`` and ``t_end``, ``stall`` the
cause of a stalled pull (``gc``, ``host``, ``runtime``) or ``None``, and
``idle_before_s`` how long the device stood idle before this step
because the host had not dispatched it: the plan asks the step in flight
``is_ready()`` after admit, after pages, after stack and before the
dispatch, and this runs from the first check that found it done to
``t_dispatch`` (0.0 when it was still running; ``None`` when no step was
in flight). It is a lower bound of the idle the host caused. ``stats()``
``waits`` keeps the stalls by cause and the five longest pulls of the
engine's life, and survives ``close()``.
"""

from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np

from vlog_tpu.lm.cache import PagedCache, SeqPages
from vlog_tpu.lm.load import LmAssets
from vlog_tpu.lm.model import (Geometry, build_step, empty_cache,
                               plan_shapes, unpack_ints)
from vlog_tpu.obs import hostwait, trace
from vlog_tpu.parallel import compile_cache
from vlog_tpu.parallel.engine_host import HOST, HeldLease

PHASES = ("admit", "pages", "stack", "dispatch", "device_wait", "deliver")
THREAD = "vlog-lm-engine"
POLL_S = 2e-4           # the pull's poll of the step's tokens


class LmJobError(RuntimeError):
    """The step that carried this request failed, or it can never fit."""


class LmRequest:
    """One prompt in the engine. ``wait()`` for the tokens."""

    def __init__(self, tag: str, prompt: np.ndarray, max_new: int,
                 eos_id: int | None, capture: tuple[int, ...]):
        self.tag = tag
        self.prompt = np.ascontiguousarray(prompt, np.int32)
        self.max_new = int(max_new)
        self.eos_id = eos_id
        # output steps whose logits to keep (0 = the prompt's last
        # position's; negative counts from the last token)
        self.capture = {c if c >= 0 else self.max_new + c for c in capture}
        self.tokens: list[int] = []
        self.logits: dict[int, np.ndarray] = {}
        self.error: BaseException | None = None
        self.stats: dict = {"t_submit": time.monotonic()}
        self._done = threading.Event()
        # engine thread only
        self.pages: SeqPages | None = None
        self.row = -1
        self.prefilled = 0          # prompt tokens planned so far
        self.planned = 0            # output tokens planned so far
        self.ended = False          # eos seen, or failed

    def wait(self, timeout: float | None = None) -> list[int]:
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.tag} is not done")
        if self.error is not None:
            raise LmJobError(str(self.error)) from self.error
        return self.tokens

    def done(self) -> bool:
        return self._done.is_set()


class LmEngine:
    def __init__(self, assets: LmAssets, *, scheduler=None,
                 geometry: Geometry | None = None):
        self.assets = assets
        self.cfg = assets.cfg
        self.scheduler = scheduler
        self.geo = geometry or default_geometry(assets.cfg)
        self.geo.check(self.cfg)
        self._lock = threading.Condition()          # lock-order: 24
        self._inbox: deque[LmRequest] = deque()     # guarded-by: _lock
        self._started = False                       # guarded-by: _lock
        self.step_log: list[dict] = []              # guarded-by: _lock
        self.requests_done = 0                      # guarded-by: _lock
        self.pool_wait_steps = 0                    # guarded-by: _lock
        self.pool_wait_rows = 0                     # guarded-by: _lock
        self._stop = threading.Event()
        self._ready = threading.Event()
        self._failed: BaseException | None = None
        self._thread: threading.Thread | None = None
        self._hold = HeldLease(scheduler)
        self._trace = trace.TraceContext(trace.new_id(), None,
                                         trace.TraceBuffer())
        self.programs: dict[int, object] = {}       # bucket -> compiled
        self.attn_forms: dict[int, str | None] = {}  # bucket -> chunk form
        self.select_forms: dict[int, str | None] = {}  # bucket -> choice
        self.attn_rows_form: str | None = None
        self.gdn_forms: dict[int, str | None] = {}  # bucket -> DeltaNet's
        self.gdn_rows_form: str | None = None
        self.combine_forms: dict[int, str | None] = {}  # bucket -> combine
        self.attn_steps = {"kernel": 0, "loop": 0}  # guarded-by: _lock
        self.combine_steps = {"kernel": 0, "xla": 0}  # guarded-by: _lock
        self.hc_defect_max = 0.0                    # guarded-by: _lock
        self.waits = hostwait.WaitBook()            # guarded-by: _lock
        # engine thread only
        self._cache: PagedCache | None = None
        self._kv = None
        self._last_tok = None
        self._waiting: deque[LmRequest] = deque()
        self._prefilling: LmRequest | None = None
        self._rows: list[LmRequest | None] = [None] * self.geo.rows
        self._flight: tuple | None = None           # (record, out)
        self._seq = 0
        self._prev_end: float | None = None
        self._prev_ready: float | None = None
        self._idle_from: float | None = None    # step in flight seen done

    # callers ----------------------------------------------------------

    def submit(self, prompt, *, max_new: int, tag: str | None = None,
               eos_id: int | None = None,
               capture: tuple[int, ...] = ()) -> LmRequest:
        """Queue one prompt (token ids); returns at once."""
        req = LmRequest(tag or trace.new_id(), prompt, max_new, eos_id,
                        capture)
        if req.max_new < 1 or req.prompt.size < 1:
            raise ValueError("a request needs a prompt and max_new >= 1")
        with self._lock:
            if self._stop.is_set():
                raise LmJobError("the engine is closed")
            self._start_locked()
            self._inbox.append(req)
            self._lock.notify_all()
        return req

    def prepare(self, timeout: float | None = None) -> None:
        """Build and run every step shape (blocks until done)."""
        with self._lock:
            self._start_locked()
        if not self._ready.wait(timeout):
            raise TimeoutError("the engine is still building")
        if self._failed is not None:
            raise LmJobError(f"engine build failed: {self._failed}") \
                from self._failed

    def program_scopes(self) -> dict[str, dict[str, str]]:
        """``{compiled program, as a device trace names it: {HLO
        instruction: named scope}}`` of the step programs, from their
        optimized HLO (``obs/profiler.py::hlo_scopes``): what a reader of
        a capture taken WITHOUT the HLO protos needs to book a device op
        to ``lm.attn.full`` or ``lm.moe.experts``. XLA's TPU lowering of
        ``ragged_dot`` names its kernels ``ragged-dot-*`` and drops the
        framework name; they are the grouped expert products. (The chunk
        attention's kernel, ``lm_chunk_attention``, keeps its ``op_name``
        and its layer's scope with it.) A latent-attention model's
        programs carry ``lm.attn.latent.project``, ``.expand``, ``.chunk``,
        ``.rows`` and ``lm.hc.map``, ``.pre``, ``.post`` the same way."""
        from vlog_tpu.obs.profiler import hlo_scopes

        out = {}
        for chunk, program in self.programs.items():
            text = program.as_text()
            scopes = hlo_scopes(text)
            for line in text.splitlines():
                name = line.strip().lstrip("%").split(" = ", 1)[0]
                if name.startswith("ragged-dot") and " = " in line:
                    scopes[name.removeprefix("ROOT ").lstrip("%")] = \
                        "lm.moe.experts"
            out[f"jit_lm_step_c{chunk}"] = scopes
        return out

    def active(self) -> bool:
        """Serving (lease held, requests queued or resident)?"""
        with self._lock:
            queued = bool(self._inbox)
        return queued or self._hold.held.is_set() or self._busy()

    def stats(self) -> dict:
        """Counts since the engine began; ``pool`` per class the pages
        that can be handed out, are handed out and are spoken for,
        ``state`` the recurrent-state slots (``slots``, ``in_use``; 0
        for a model without DeltaNet layers),
        ``pool_wait`` the steps in which, and the rows that, stood empty
        for want of pages (summed over those steps), ``attn`` the steps
        whose chunk attended in each form (``kernel_steps``, ...) and,
        for a model with an indexer, chose its keys in each
        (``select_kernel_steps``, ``select_loop_steps``),
        ``attn_rows_form`` the form of
        the rows, ``combine_kernel_steps`` / ``combine_xla_steps`` the
        steps whose experts' rows were summed back in each form, and
        ``hc_defect_max`` the largest ``hc_defect`` of any step (0.0 for
        a model with one residual stream), ``waits`` the pulls' stalls by
        cause and the five longest (``obs/hostwait.py::WaitBook``)."""
        cache = self._cache
        with self._lock:
            return {"steps": len(self.step_log),
                    "hc_defect_max": self.hc_defect_max,
                    "requests_done": self.requests_done,
                    "pending": len(self._inbox),
                    "pages_in_use": cache.in_use()
                    if cache else {"window": 0, "full": 0},
                    "pool": cache.pools() if cache else {},
                    "state": cache.state() if cache else {},
                    "pool_wait": {"steps": self.pool_wait_steps,
                                  "rows": self.pool_wait_rows},
                    "attn": {f"{form}_steps": n
                             for form, n in self.attn_steps.items()},
                    "attn_rows_form": self.attn_rows_form,
                    **{f"combine_{form}_steps": n
                       for form, n in self.combine_steps.items()},
                    "waits": self.waits.stats()}

    def close(self) -> None:
        self._stop.set()
        with self._lock:
            self._lock.notify_all()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=60)
        self._kv = self._last_tok = None
        self.programs = {}

    def _start_locked(self) -> None:
        if self._started:
            return
        self._started = True
        hostwait.GC.install()           # once a process
        self._thread = trace.start_thread(self._trace, self._run,
                                          name=THREAD)

    # the engine's thread ---------------------------------------------

    def _run(self) -> None:
        try:
            self._build()
        except BaseException as exc:  # noqa: BLE001 — surfaced by prepare
            self._failed = exc
            self._ready.set()
            self._fail_all(exc)
            return
        self._ready.set()
        try:
            while not self._stop.is_set():
                self._cycle()
        finally:
            self._fail_all(LmJobError("the engine closed"))
            self._hold.release()
            self._trace.buffer.drain()

    def _build(self) -> None:
        import jax
        import jax.numpy as jnp

        compile_cache.ensure_compile_cache()
        cfg, geo = self.cfg, self.geo
        self._cache = PagedCache(cfg, geo)
        self._kv = empty_cache(cfg, geo)
        self._last_tok = jnp.zeros((geo.rows,), jnp.int32)
        for chunk in geo.chunk_buckets():
            plan = {k: jax.ShapeDtypeStruct(s, d)
                    for k, (s, d) in plan_shapes(cfg, geo, chunk).items()}
            step = build_step(cfg, geo, chunk)
            self.attn_forms[chunk] = step.attn_chunk_form
            self.select_forms[chunk] = step.attn_select_form
            self.attn_rows_form = step.attn_rows_form
            self.gdn_forms[chunk] = step.gdn_chunk_form
            self.gdn_rows_form = step.gdn_rows_form
            self.combine_forms[chunk] = step.moe_combine_form
            fn = jax.jit(step, donate_argnums=(1, 2))
            self.programs[chunk] = fn.lower(
                self.assets.params, self._kv, self._last_tok, plan).compile()
        for chunk, program in self.programs.items():
            plan = {k: np.zeros(s, d)
                    for k, (s, d) in plan_shapes(cfg, geo, chunk).items()}
            if chunk:
                plan["chunk_meta"][2] = -1
            self._kv, self._last_tok, out = program(
                self.assets.params, self._kv, self._last_tok, plan)
            np.asarray(out["ints"])

    def _busy(self) -> bool:
        return (self._prefilling is not None or bool(self._waiting)
                or self._flight is not None
                or any(r is not None for r in self._rows))

    def _cycle(self) -> None:
        """One iteration: plan and dispatch step ``n``, then pull and
        deliver step ``n - 1``."""
        built0 = compile_cache.thread_built_s()
        record = None
        self._idle_from = None
        with trace.span("lm.step") as top:
            with trace.span("lm.step.admit"):
                with self._lock:
                    if not self._inbox and not self._busy():
                        self._lock.wait(0.2)
                    while self._inbox:
                        self._waiting.append(self._inbox.popleft())
                if not self._busy():
                    self._hold.release()    # idle: give the slot back
                    self._prev_end = self._prev_ready = None
                    self._trace.buffer.drain()
                    return
                if not self._hold.acquire(self._stop):
                    return
            self._probe()
            try:
                step = self._plan()
                if step is not None:
                    record, plan = step
                    with trace.span("lm.step.dispatch"):
                        program = self.programs[record["chunk"]]
                        self._probe()
                        self._kv, self._last_tok, out = program(
                            self.assets.params, self._kv, self._last_tok,
                            plan)
                    record["t_dispatch"] = time.monotonic()
                    record["gap_s"] = (
                        None if self._prev_end is None
                        else record["t_dispatch"] - self._prev_end)
                    record["idle_before_s"] = (
                        None if self._flight is None
                        else 0.0 if self._idle_from is None
                        else record["t_dispatch"] - self._idle_from)
                prev, self._flight = self._flight, (
                    None if step is None else (record, out))
                if prev is not None:
                    self._deliver(*prev)
            except Exception as exc:  # noqa: BLE001 — the engine survives
                self._fail_all(exc)
                self._reset_device_state()
                return
        spans = self._trace.buffer.drain()
        now = time.monotonic()
        self._prev_end = now
        if record is not None:
            record["build_s"] = compile_cache.thread_built_s() - built0
            record["t_start"] = top.started_mono
            record["host_phase_s"] = self._phases(spans)
        if prev is not None:
            done = prev[0]
            done["t_end"] = now
            done["gc_s"] = hostwait.GC.seconds_between(done["t_start"], now)
            # the pull and the delivery of a step happen one iteration
            # after its plan: fold them into the step they belong to
            mine = self._phases(spans)
            done["phase_s"] = {**done.pop("host_phase_s"),
                               "device_wait": mine["device_wait"],
                               "deliver": mine["deliver"]}
            with self._lock:
                self.step_log.append(done)
                if done["pool_wait_rows"]:
                    self.pool_wait_steps += 1
                    self.pool_wait_rows += done["pool_wait_rows"]
                forms = [done["attn_chunk_form"]]
                if done["attn_select_form"]:
                    forms.append(f"select_{done['attn_select_form']}")
                for form in filter(None, forms):
                    self.attn_steps[form] = self.attn_steps.get(form, 0) + 1
                if done["moe_combine_form"]:
                    self.combine_steps[done["moe_combine_form"]] += 1
                self.hc_defect_max = max(self.hc_defect_max,
                                         done.get("hc_defect", 0.0))
                done["stall"] = self.waits.add(done["wait"],
                                               key=done["chunk"],
                                               seq=done["seq"])
            self._observe(done)
        if self._flight is None:
            self._hold.yield_full_mesh()

    def _probe(self) -> None:
        """A phase boundary of the plan: note when the step in flight
        is first seen done (``idle_before_s``; at most four calls a
        step)."""
        if self._idle_from is None and self._flight is not None \
                and self._flight[1]["ints"].is_ready():
            self._idle_from = time.monotonic()

    @staticmethod
    def _phases(spans) -> dict:
        return trace.add_phase_seconds(dict.fromkeys(PHASES, 0.0), spans,
                                       under="lm.step.")

    # planning ----------------------------------------------------------

    def _next_prefill(self) -> LmRequest | None:
        """The request whose chunk this step carries: the one in prefill,
        else the first waiting one that a free row and the pools can
        hold to its end."""
        if self._prefilling is not None:
            return self._prefilling
        while self._waiting:
            req = self._waiting[0]
            total = req.prompt.size + req.max_new
            if not self._cache.fits_ever(total):
                self._waiting.popleft()
                self._finish(req, LmJobError(
                    f"request {req.tag}: {total} positions exceed the "
                    f"cache (cap {self._cache.context_cap})"))
                continue
            if None not in self._rows:
                return None
            row = self._rows.index(None)
            pages = self._cache.admit(total, row)
            if pages is None:
                return None
            self._waiting.popleft()
            req.pages = pages
            req.row = row
            self._rows[req.row] = req           # reserved; decodes later
            req.stats["t_admit"] = time.monotonic()
            self._prefilling = req
            return req
        return None

    def _plan(self):
        """Decide step ``n`` from what the host knows; ``None`` when
        there is nothing to run. Updates the requests' planned state."""
        geo = self.geo
        r = geo.rows
        # a row whose request ended early or is complete leaves now
        for req in self._rows:
            if req is not None and req is not self._prefilling and (
                    req.ended or req.planned >= req.max_new):
                self._leave(req)
        freed = 0
        with trace.span("lm.step.pages"):
            pre = self._next_prefill()
            # nobody was admitted although a row is free and a request
            # waits: it waits for pages, and the rows that the waiting
            # requests would have taken stand empty
            starved = 0 if pre is not None else min(
                self._rows.count(None), len(self._waiting))
            deco = [req for req in self._rows
                    if req is not None and req is not self._prefilling]
            if pre is None and not deco:
                return None
            n = bucket = 0
            if pre is not None:
                p0 = pre.prefilled
                n = min(geo.chunk, pre.prompt.size - p0)
                bucket = next(b for b in geo.chunk_buckets() if b >= n)
                freed += pre.pages.trim(p0)
                pre.pages.extend(p0 + n)
            for req in deco:
                pos = req.prompt.size + req.planned - 1
                freed += req.pages.trim(pos)
                req.pages.extend(pos + 1)
        self._probe()
        with trace.span("lm.step.stack"):
            shapes = plan_shapes(self.cfg, geo, bucket)
            plan = {k: np.zeros(s, d) for k, (s, d) in shapes.items()}
            windowed = "row_wtab" in plan
            emitted, captures = [], []
            for req in deco:
                i = req.row
                pos = req.prompt.size + req.planned - 1
                wtab, wbase, ftab = req.pages.tables()
                plan["row_active"][i] = True
                plan["row_pos"][i] = pos
                if windowed:
                    plan["row_wtab"][i], plan["row_wbase"][i] = wtab, wbase
                plan["row_ftab"][i] = ftab
                if req.planned in req.capture:
                    captures.append((req, req.planned, i))
                emitted.append((req, i))
                req.planned += 1
            last_chunk = False
            if pre is not None:
                last_chunk = p0 + n >= pre.prompt.size
                wtab, wbase, ftab = pre.pages.tables()
                plan["chunk_ids"][:n] = pre.prompt[p0:p0 + n]
                plan["chunk_meta"][:] = (p0, n, pre.row if last_chunk
                                         else -1, wbase)
                plan["chunk_ftab"] = ftab
                if windowed:
                    plan["chunk_wtab"] = wtab
                if "chunk_slot" in plan:
                    plan["chunk_slot"][0] = pre.pages.slot
                pre.prefilled += n
                pre.stats.setdefault("t_first_chunk", time.monotonic())
                pre.stats["prefill_steps"] = pre.stats.get(
                    "prefill_steps", 0) + 1
                if last_chunk:
                    if 0 in pre.capture:
                        captures.append((pre, 0, r))
                    emitted.append((pre, r))
                    pre.planned = 1
                    self._prefilling = None
        self._probe()
        record = {"seq": self._seq, "chunk": bucket,
                  "decode_rows": len(deco), "prefill_tokens": n,
                  "row_pos": plan["row_pos"][plan["row_active"]].tolist(),
                  "context": p0 if pre is not None else None,
                  "attn_chunk_form": self.attn_forms[bucket],
                  "attn_select_form": self.select_forms[bucket],
                  "attn_rows_form": self.attn_rows_form,
                  "gdn_chunk_form": self.gdn_forms[bucket],
                  "gdn_rows_form": self.gdn_rows_form,
                  "moe_combine_form": self.combine_forms[bucket],
                  "state_slots": self._cache.slots.in_use,
                  "rows_context": int(plan["row_pos"][
                      plan["row_active"]].sum() + len(deco)),
                  "chunk_tag": pre.tag if pre is not None else None,
                  "emitted": [req.tag for req, _ in emitted],
                  "pages_in_use": self._cache.in_use(),
                  "pages_freed": freed,
                  "pool_wait_rows": starved,
                  "_emitted": emitted, "_captures": captures}
        self._seq += 1
        return record, plan

    def _leave(self, req: LmRequest) -> None:
        if req.pages is not None:
            req.stats["peak_window_pages"] = req.pages.peak_window_pages
            req.pages.release()
            req.pages = None
        if req.row >= 0 and self._rows[req.row] is req:
            self._rows[req.row] = None

    # delivery ------------------------------------------------------------

    def _deliver(self, record: dict, out: dict) -> None:
        with trace.span("lm.step.device_wait"):
            # polled, not pulled with a blocking wait: about one window
            # in seven lost 2.2 to 2.5 s in ONE blocking pull of these
            # counters while the device had long finished this step and
            # the next (3 of 19 runs; none of 8 polled; my chip runs,
            # PR 33: not proof, PERF.md section 7); the wait record says
            # which of the thread, a collection or the runtime held it
            (ints,), record["wait"] = hostwait.pull((out["ints"],),
                                                    poll_s=POLL_S)
            ints = unpack_ints(self.cfg, self.geo, ints)
        ready = time.monotonic()
        record["t_ready"] = ready
        begun = record["t_dispatch"] if self._prev_ready is None \
            else max(self._prev_ready, record["t_dispatch"])
        record["step_s"] = ready - begun
        self._prev_ready = ready
        record["expert_load"] = ints["expert_load"].tolist()
        if "keys" in ints:
            record["sparse_keys"] = ints["keys"].tolist()
        elif "hc_defect" in ints:
            record["hc_defect"] = ints["hc_defect"]
        elif "held_choices" in ints:
            record["held_choices"] = ints["held_choices"].tolist()
        else:
            record["window_pages"] = ints["pages"].tolist()
        with trace.span("lm.step.deliver"):
            captures = [c for c in record.pop("_captures") if not c[0].ended]
            if captures:
                # the whole array in one transfer of a buffer that is
                # ready: a program that sliced a row on the device would
                # queue behind the step already dispatched and hold the
                # host back a whole step (device idle 23% with every
                # step of six requests kept; my chip run, PR 29)
                logits = np.asarray(out["logits"])
                for req, index, row in captures:
                    req.logits[index] = logits[row].copy()
            for req, row in record.pop("_emitted"):
                if req.ended:
                    continue            # ended early: dropped
                tok = int(ints["tokens"][row])
                req.tokens.append(tok)
                if len(req.tokens) == 1:
                    req.stats["t_first_token"] = ready
                if len(req.tokens) >= req.max_new or tok == req.eos_id:
                    self._finish(req, None)

    def _finish(self, req: LmRequest, error: BaseException | None) -> None:
        if req.done():
            return
        req.ended = True
        req.error = error
        req.stats["t_done"] = time.monotonic()
        if error is None:
            with self._lock:
                self.requests_done += 1
        req._done.set()

    def _fail_all(self, exc: BaseException) -> None:
        with self._lock:
            pending = list(self._inbox)
            self._inbox.clear()
        flight = self._flight[0]["_emitted"] if self._flight else []
        for req in {*pending, *self._waiting, *(r for r, _ in flight),
                    *(r for r in self._rows if r is not None)}:
            self._finish(req, exc)
        self._waiting.clear()
        self._flight = None
        self._prefilling = None
        for req in list(self._rows):
            if req is not None:
                self._leave(req)

    def _reset_device_state(self) -> None:
        """After a failed step the donated buffers are gone: new ones."""
        import jax.numpy as jnp

        self._kv = empty_cache(self.cfg, self.geo)
        self._last_tok = jnp.zeros((self.geo.rows,), jnp.int32)
        self._prev_end = self._prev_ready = None

    def _observe(self, record: dict) -> None:
        try:
            from vlog_tpu.obs.metrics import runtime

            m = runtime()
            m.device_seconds.labels("lm", "step").inc(
                record["phase_s"]["device_wait"])
            if record["stall"] is not None:
                m.engine_stalls.labels("lm", record["stall"]).inc()
        except Exception:  # noqa: BLE001 — metrics never break serving
            pass


POOL_BYTES = 4 << 30        # the most a default pool takes of the chip


def default_geometry(cfg) -> Geometry:
    """``Geometry``'s defaults (32 rows, chunks of 2048, pages of 256,
    a context cap of 40,960) with the pools sized so that every row can
    hold the context cap, where that stays under ``POOL_BYTES`` a class;
    a model whose every layer is of the full class (``KeyeVL2``: 13 KB a
    position over six layers; ``xing4_0``'s latents: 6.9 KB) gets the
    pages that fit, and its requests wait for pages with rows to spare.
    No window layers, no window pool."""
    base = Geometry()
    window_b, full_b = cfg.position_bytes()

    def pages(per_row: int, position_bytes: int) -> int:
        if not position_bytes:
            return 0
        return min(base.rows * per_row,
                   POOL_BYTES // (base.page * position_bytes)) + 1

    return Geometry(
        window_pages=pages(base.ring(cfg.sliding_window), window_b),
        full_pages=pages(base.max_pages, full_b))


# The process's engine (parallel/engine_host.py holds it) --------------

def get_engine(model_dir: str, *, scheduler=None) -> LmEngine:
    """The process's transcript engine, (re)built when the model
    directory or the scheduler changes. One model engine is resident on
    a worker at a time and one is built at a time: the host first waits
    for an ``AsrEngine`` to go idle and closes it."""
    from vlog_tpu.lm.load import load_model_dir

    return HOST.obtain(
        "lm", (str(model_dir), id(scheduler)),
        lambda: LmEngine(load_model_dir(model_dir), scheduler=scheduler))


def peek_engine() -> LmEngine | None:
    return HOST.peek("lm")


def reset_engine() -> None:
    HOST.evict("lm")
