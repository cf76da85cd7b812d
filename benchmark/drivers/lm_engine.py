"""Driver ``lm_engine``: digests through the production entry.

The window drives ``worker/digest.py::digest_tokens`` (the function the
digest job calls between its tokenizer and its files) from client
threads against ONE ``LmEngine`` built on weights made on the device
from the seed, with token ids in place of cue text, as ``asr_engine``
drives ``transcribe_audio_engine``. It reads the engine's own step
records (``step_log``) and programs; it plans no step and touches no
page table.

**Work that is the same on every seed.** The playlist comes from the
traffic file's ``schedule_seed`` (``generators/transcript_backlog.py``);
``--seed`` makes the weights, the instruction's ids and each
transcript's ids and nothing else. Clients take the next recording from
one shared cursor, and a client holds the cursor's lock until its
request is in the engine, so requests enter in playlist order.

**The window** opens at the step boundary at which the
``open_when_finished``-th request got its last token and closes at the
first step boundary at or after ``--seconds``. ``audio_s_per_s``: each
request's audio seconds spread evenly over its prompt and output
tokens, credited in the step that prefills or emits them (the step
records name the request of every chunk and of every token), summed
over the window's steps, over the window's length.

**correct**: from the first finished clip, talk and stream that entered
after the window opened, the logits the timed path itself produced at
the prompt's last position and at every output step against ``reference/afmoe_ref.py``'s full forward pass over prompt plus
served tokens (the engine's state is freed first).
"""

from __future__ import annotations

import glob
import sys
import threading
import time

import numpy as np

from drivers.asr_engine import effective, verdict

POLL_S = 0.02
_T0 = time.perf_counter()


def log(stage: str) -> None:
    """One line of standard error per stage of a run, with the seconds
    since the process began: where a run that hangs had got to."""
    print(f"lm_engine: {time.perf_counter() - _T0:8.1f} s {stage}",
          file=sys.stderr, flush=True)
MODEL_KEYS_SKIP = ("source", "driver", "reduced", "cut", "assumed", "deployment",
                   "check", "rehearsal", "published_num_hidden_layers")


class Item:
    """One request of the run."""

    def __init__(self, index: int, spec: dict, ids: np.ndarray):
        self.index, self.spec, self.ids = index, spec, ids
        self.tag = f"r{index}"
        self.status = "running"         # ok | cut | failed
        self.error = ""
        self.request = None
        self.stats: dict = {}


class _Gate:
    """What ``digest_tokens`` sees as its engine: the real one, with the
    cursor's lock let go once the request is in."""

    def __init__(self, engine, lock: threading.Lock):
        self._engine, self._lock = engine, lock
        self.released = False

    def release(self) -> None:
        if not self.released:
            self.released = True
            self._lock.release()

    def submit(self, *args, **kw):
        try:
            return self._engine.submit(*args, **kw)
        finally:
            self.release()


class Run:
    def __init__(self, cell, opts):
        self.cell, self.opts = cell, opts
        self.cfg = effective(cell.config, opts.rehearse)
        self.traffic = effective(cell.traffic, opts.rehearse)
        self.dep = self.cfg["deployment"]
        self.model = {k: v for k, v in self.cfg.items()
                      if k not in MODEL_KEYS_SKIP}
        self.items: list[Item] = []
        self.items_lock = threading.Lock()
        self.cursor_lock = threading.Lock()
        self.cursor = 0
        self.closing = threading.Event()
        self.parts: dict = {}

    # ---- set-up ----------------------------------------------------------

    def build(self):
        self.parts["before_build_s"] = time.perf_counter() - self.opts.t_start
        import jax

        from models.afmoe_weights import make_params
        from vlog_tpu.lm.engine import LmEngine
        from vlog_tpu.lm.load import LmAssets
        from vlog_tpu.lm.model import Geometry, LmConfig
        from vlog_tpu.worker import digest

        self.jax, self.digest = jax, digest
        t0 = time.monotonic()
        self.params = make_params(self.model, self.opts.seed)
        jax.block_until_ready(self.params)
        self.parts["weights_s"] = time.monotonic() - t0
        log("weights made")
        geo = Geometry(**{k: int(self.dep[k]) for k in (
            "rows", "chunk", "page", "context_cap", "kv_block_pages",
            "window_pages", "full_pages")})
        assets = LmAssets(cfg=LmConfig.from_hf(self.model),
                          params=self.params, tokenizer=None,
                          model_name=self.cell.config_name)
        self.engine = LmEngine(assets, geometry=geo)
        t0 = time.monotonic()
        self.engine.prepare()
        self.parts["prepare_s"] = time.monotonic() - t0
        log("engine prepared")
        rng = np.random.default_rng([int(self.opts.seed), 31])
        self.instruction = rng.integers(
            0, self.model["vocab_size"],
            int(self.traffic["params"]["instruction_tokens"]),
            dtype=np.int32)

    def ids_of(self, index: int, spec: dict) -> np.ndarray:
        """The instruction, then the recording's transcript: ids uniform
        over the vocabulary from (seed, place in the playlist)."""
        n = spec["prompt_tokens"] - self.instruction.size
        rng = np.random.default_rng(
            [int(self.opts.seed), 37, index % len(self.plan["playlist"])])
        return np.concatenate([self.instruction, rng.integers(
            0, self.model["vocab_size"], n, dtype=np.int32)])

    # ---- traffic ---------------------------------------------------------

    def client(self) -> None:
        playlist = self.plan["playlist"]
        while not self.closing.is_set():
            self.cursor_lock.acquire()
            index = self.cursor
            self.cursor += 1
            spec = playlist[index % len(playlist)]
            item = Item(index, spec, self.ids_of(index, spec))
            with self.items_lock:
                self.items.append(item)
            gate = _Gate(self.engine, self.cursor_lock)
            try:
                item.request = self.digest.digest_tokens(
                    gate, item.ids, max_new=spec["output_tokens"],
                    job_key=item.tag, stats_out=item.stats,
                    capture=tuple(range(spec["output_tokens"]))
                    if index in self.watch else ())
                item.status = "ok"
            except Exception as e:  # noqa: BLE001 — a failed job is a count
                item.status = "cut" if self.closing.is_set() else "failed"
                item.error = f"{type(e).__name__}: {e}"
                gate.release()          # where the submit never came

    def pick_watched(self) -> set[int]:
        """Playlist places whose logits the run keeps: the first two of
        each class that enter after the window can have opened."""
        playlist = self.plan["playlist"]
        first = self.plan["clients"] + self.plan["open_when_finished"]
        out: set[int] = set()
        for kind in {p["kind"] for p in playlist}:
            found = [i for i in range(first, first + 2 * len(playlist))
                     if playlist[i % len(playlist)]["kind"] == kind][:2]
            out.update(found)
        return out

    def serve(self, tracer) -> tuple[dict, list[dict]]:
        """Start the clients, find the window's opening step, let
        ``--seconds`` pass, find the closing step; returns the window
        and its step records."""
        self.watch = self.pick_watched()
        threads = [threading.Thread(target=self.client, daemon=True,
                                    name=f"bench-client-{i}")
                   for i in range(self.plan["clients"])]
        for t in threads:
            t.start()
        need = {f"r{i}": self.plan["playlist"][i % len(
            self.plan["playlist"])]["output_tokens"] for i in range(
                self.plan["clients"] + 4 * len(self.plan["playlist"]))}
        got: dict[str, int] = {}
        finished = seen = 0
        open_at = close_at = None       # indices into step_log
        t_open = None
        limit = time.monotonic() + 600.0 + self.opts.seconds
        steps = self.engine.step_log
        while close_at is None:
            if time.monotonic() > limit:
                raise RuntimeError("the window never closed")
            time.sleep(POLL_S)
            while seen < len(steps) and close_at is None:
                rec = steps[seen]
                seen += 1
                for tag in rec["emitted"]:
                    got[tag] = got.get(tag, 0) + 1
                    if got[tag] == need.get(tag):
                        finished += 1
                if open_at is None:
                    if finished >= self.plan["open_when_finished"]:
                        open_at, t_open = seen, rec["t_ready"]
                        self.parts["setup_s"] = (
                            time.perf_counter() - self.opts.t_start
                            - (time.monotonic() - t_open))
                        self.trace_stretch(tracer, t_open)
                        log(f"window open at step {seen}")
                elif rec["t_ready"] >= t_open + self.opts.seconds:
                    close_at = seen
        self.closing.set()
        records = list(steps[open_at:close_at])
        window = {"t0": t_open, "t_end": records[-1]["t_ready"],
                  "open_step": open_at, "close_step": close_at}
        self.threads = threads
        return window, records

    def trace_stretch(self, tracer, t_open: float) -> None:
        """A traced run profiles ``trace_seconds`` of the window from
        ``trace_after_s`` on, from a thread of its own."""
        self.trace_span = None
        if not tracer.enabled:
            return

        def control():
            time.sleep(max(0.0, t_open + float(
                self.traffic["trace_after_s"]) - time.monotonic()))
            tracer.start()
            t0 = time.monotonic()
            time.sleep(float(self.traffic["trace_seconds"]))
            t1 = time.monotonic()
            tracer.stop_now()
            self.trace_span = (t0, t1)

        self.tracer_thread = threading.Thread(
            target=control, name="bench-trace-control", daemon=True)
        self.tracer_thread.start()

    def stop_traffic(self) -> None:
        self.engine.close()             # fails what is still in flight
        for t in self.threads:
            t.join(10.0)

    def free_program(self) -> None:
        self.engine = None
        self.jax.clear_caches()


# --------------------------------------------------------------------------
# after the window
# --------------------------------------------------------------------------

def audio_credit(records: list[dict], by_tag: dict) -> float:
    """Audio seconds the steps carried: per chunk its tokens, per token
    emitted one, each times its request's seconds a token."""
    from generators.transcript_backlog import credit_per_token

    total = 0.0
    for rec in records:
        if rec["prefill_tokens"]:
            total += rec["prefill_tokens"] * credit_per_token(
                by_tag[rec["chunk_tag"]])
        for tag in rec["emitted"]:
            total += credit_per_token(by_tag[tag])
    return total


def step_summary(records: list[dict], span_s: float) -> dict:
    """Where a window's seconds went, from the step records: a run that
    reads low says here whether the device's steps were slower (``ms``
    by chunk bucket), the host dispatched late (``host_late_s``: the
    window less the steps' own seconds) or a few steps stalled
    (``slowest``, each with the device waits of the two steps after)."""
    by_chunk: dict[int, list[float]] = {}
    for rec in records:
        by_chunk.setdefault(rec["chunk"], []).append(rec["step_s"])
    slowest = sorted(records, key=lambda rec: -rec["step_s"])[:5]
    at = {id(rec): i for i, rec in enumerate(records)}

    def wait_after(rec: dict) -> list[float]:
        # the device had the next step already: if that one's wait is
        # near 0 the host slept while the device went on and then idled;
        # if it is a step's usual time the device itself stood still
        return [1e3 * r["phase_s"]["device_wait"]
                for r in records[at[id(rec)] + 1:at[id(rec)] + 3]]

    return {
        "host_late_s": span_s - sum(rec["step_s"] for rec in records),
        "ms_by_chunk": {str(c): {"steps": len(v),
                                 "mean": 1e3 * sum(v) / len(v),
                                 "max": 1e3 * max(v)}
                        for c, v in sorted(by_chunk.items())},
        "phase_s": {k: sum(rec["phase_s"][k] for rec in records)
                    for k in records[0]["phase_s"]},
        "slowest": [{"at_s": rec["t_ready"] - records[0]["t_ready"],
                     "chunk": rec["chunk"], "context": rec["context"],
                     "ms": 1e3 * rec["step_s"],
                     "gap_ms": 1e3 * (rec["gap_s"] or 0.0),
                     "wait_after_ms": wait_after(rec),
                     "phase_ms": {k: 1e3 * v
                                  for k, v in rec["phase_s"].items()}}
                    for rec in slowest]}


def picked_items(run: Run) -> dict:
    """The first finished watched request of each class."""
    done = sorted((it for it in run.items if it.status == "ok"
                   and it.index in run.watch and it.request.logits),
                  key=lambda it: it.index)
    picked: dict = {}
    for it in done:
        picked.setdefault(it.spec["kind"], it)
    return picked


def reference_rows(item: Item, params, model: dict, **how) -> dict:
    """The plain reference's full forward pass over the request's prompt
    plus served tokens, at the positions whose logits the run kept."""
    from reference import afmoe_ref as ref

    req = item.request
    steps = sorted(req.logits)
    full = np.concatenate([item.ids, np.asarray(req.tokens[:-1], np.int32)])
    out = ref.forward(params, model, full,
                      [item.ids.size - 1 + s for s in steps], **how)
    return {"steps": steps, "tokens": int(full.size), **out}


def readings(item: Item, rows: dict, logits_of, chk: dict,
             token_of=None) -> dict:
    """``logits_of(step)`` (and the token ``token_of(step)`` chosen from
    them; the served one where not given) against the reference's rows. A
    position whose
    router margin (the smallest, over the expert layers, of the k-th
    biased score over the (k+1)-th) is under ``route_eps`` can fall
    either way on bfloat16 rounding, and a top-k choice that falls the
    other way moves the logits by their whole spread: such positions are
    left out of ``errs`` and ``gaps`` and counted as ``ties``; ``flipped``
    counts the positions, of all, whose error passes the logit limit."""
    from reference import afmoe_ref as ref

    errs, gaps, kept, ties, flipped = [], [], [], 0, 0
    if token_of is None:
        token_of = item.request.tokens.__getitem__
    for row, s in enumerate(rows["steps"]):
        e = ref.logit_error(logits_of(s), rows["logits"][row])
        flipped += e > chk["logit_err"]
        if rows["route_gap"][row] < chk["route_eps"]:
            ties += 1
            continue
        errs.append(e)
        gaps.append(ref.rank_gap(token_of(s), rows["logits"][row]))
        kept.append([round(float(rows["route_gap"][row]), 6), round(e, 5)])
    return {"errs": errs, "gaps": gaps, "ties": ties, "flipped": flipped,
            "n": len(rows["steps"]), "kept": kept}


def compared_of(run: Run, by_kind: dict) -> dict:
    """The compared numbers, each beside its limit, from the readings of
    every kind (``readings``): the worst error and gap over the compared
    positions, the shares over all positions."""
    chk = run.cfg["check"]
    got = list(by_kind.values())
    errs = [e for g in got for e in g["errs"]]
    gaps = [x for g in got for x in g["gaps"]]
    n = sum(g["n"] for g in got)
    failed = sum(1 for it in run.items if it.status == "failed")
    built = sum(r["build_s"] for r in run.window_records)
    return {
        "positions_compared": {"value": len(errs),
                               "limit": f">={chk['min_positions']}"},
        "kinds_compared": {"value": len(by_kind),
                           "limit": f">={chk.get('min_kinds', 1)}"},
        "logit_err": {"value": max(errs) if errs else 1e30,
                      "limit": chk["logit_err"]},
        "beam_rank_gap": {"value": max(gaps) if gaps else 1e30,
                          "limit": chk["beam_rank_gap"]},
        "route_tie_share": {"value": sum(g["ties"] for g in got) / n
                            if n else 1.0,
                            "limit": chk["route_tie_share"]},
        "flipped_share": {"value": sum(g["flipped"] for g in got) / n
                          if n else 1.0,
                          "limit": chk["flipped_share"]},
        "requests_failed_or_never_finished": {"value": failed, "limit": 0},
        "seconds_building_in_window": {"value": built, "limit": 0},
    }


def check(run: Run) -> dict:
    """Served logits against the plain reference (module docstring)."""
    by_kind, detail = {}, {}
    for kind, it in sorted(picked_items(run).items()):
        rows = reference_rows(it, run.params, run.model)
        log(f"reference done: {kind}, {rows['tokens']} tokens")
        got = readings(it, rows, it.request.logits.__getitem__,
                       run.cfg["check"])
        by_kind[kind] = got
        # per compared position: the router's margin, the logit error
        detail[kind] = {"index": it.index, "tokens": rows["tokens"],
                        "positions": got["n"], "ties": got["ties"],
                        "flipped": got["flipped"],
                        "margin_and_err": got["kept"]}
    run.check_detail = detail
    return compared_of(run, by_kind)


def scope_seconds(run: Run, tracer) -> dict | None:
    """Device seconds by the program's named scopes over the traced
    stretch (``readers/_lm_trace.py``), read before the harness reduces
    and deletes the capture."""
    if not tracer.enabled:
        return None
    run.tracer_thread.join()
    found = glob.glob(str(tracer.dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    scopes = getattr(run.engine, "program_scopes", None)
    if not found or scopes is None:
        return None
    from harness.trace import load
    from readers._lm_trace import by_scope

    return by_scope(load(found[0]), scopes())


def run(cell, opts, tracer) -> dict:
    from harness.spec import plugin

    r = Run(cell, opts)
    gen = plugin("generators", r.traffic["generator"])
    r.plan = gen.generate(r.traffic["params"], seed=opts.seed,
                          seconds=opts.seconds)
    r.build()
    window, records = r.serve(tracer)
    log(f"window closed at step {window['close_step']}")
    r.window_records = records
    scope_s = scope_seconds(r, tracer)
    log("capture read")
    tracer.stop_now()
    mem = [d.memory_stats() or {} for d in r.jax.local_devices()[:cell.chips]]
    fullest = max(mem, key=lambda m: m.get("peak_bytes_in_use", 0)
                  + m.get("peak_bytes_reserved", 0))
    peak = (fullest.get("peak_bytes_in_use", 0)
            + fullest.get("peak_bytes_reserved", 0))
    engine_stats = r.engine.stats()
    r.stop_traffic()
    log("engine closed")
    r.free_program()
    log("program freed")

    t0 = time.monotonic()
    compared = check(r)
    check_s = time.monotonic() - t0

    by_tag = {it.tag: it.spec for it in r.items}
    span_s = window["t_end"] - window["t0"]
    audio = audio_credit(records, by_tag)
    items = r.items
    trace_steps = []
    if r.trace_span is not None:
        a, b = r.trace_span
        trace_steps = [rec for rec in records
                       if a <= rec["t_ready"] <= b]
    return {
        "correct": verdict(compared), "compared": compared,
        "attempted": sum(1 for it in items if it.status != "cut"),
        "failed": sum(1 for it in items if it.status == "failed"),
        "end_to_end": {"audio_s_per_s": audio / span_s,
                       "setup_s": r.parts["setup_s"]},
        "memory_peak_bytes": int(peak),
        "extra": {
            "window_s": span_s, "steps": len(records),
            "step_summary": step_summary(records, span_s),
            "audio_s": audio,
            "tokens": {"prefill": sum(x["prefill_tokens"] for x in records),
                       "decode": sum(x["decode_rows"] for x in records)},
            "requests": {s: sum(1 for it in items if it.status == s)
                         for s in ("ok", "cut", "failed")},
            "open_step": window["open_step"],
            "close_step": window["close_step"],
            "engine_stats": engine_stats,
            "memory_parts": {k: fullest.get(k) for k in (
                "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")},
            "setup_parts_s": r.parts, "check_s": check_s,
            "check_detail": r.check_detail,
            "by_scope_s": scope_s,
            "trace_steps": len(trace_steps),
            "errors": sorted({it.error for it in items
                              if it.status == "failed"})[:5],
        },
        "layer_ctx": {"step_log": records, "trace_steps": trace_steps,
                      # asr_occupancy.backlog and asr_tick_ms.backlog
                      # carry no list of cells (a test of the harness adds
                      # a cell and expects them unasked), so every cell
                      # that reports audio_s_per_s has to report them: a
                      # step is this engine's tick, its decoding rows the
                      # rows that carried work
                      "batch_log": [{"n": x["decode_rows"],
                                     "rows": int(r.dep["rows"]),
                                     "elapsed_s": x["step_s"]}
                                    for x in records],
                      "scope_s": scope_s, "model": r.model,
                      "window": window},
    }
