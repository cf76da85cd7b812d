"""Driver ``asr_engine``: captions through the production entry.

The window drives ``worker/transcribe.py::transcribe_audio_engine`` from
job threads against ONE ``AsrEngine`` built on weights made on the
device from the seed, exactly as ``transcribe_video`` drives it after
audio extraction: language left to the per-job pass, beam, batch bucket
and tick from the configuration's deployment settings (set in the
environment before the program is imported, the way an operator sets
them).

What the benchmark takes from the program: that entry, the engine class
and its ``batch_log`` / ``stats()`` counters, ``format_vtt``, and two
layer boundaries it records its own spans around (and reads the model
step's outputs at): ``asr.decode.generate_batch`` and
``asr.mel.log_mel_spectrogram``. Everything else (traffic, audio,
weights, tokenizer stand-in, spans, reduction, reference, comparison) is
under ``benchmark/``.
"""

from __future__ import annotations

import math
import os
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from harness import stats as st
from harness.spans import Recorder

SR = 16_000
SETTLE_S = 0.1          # a tick's deliveries land within this of each other
LATE_WAIT_S = 60.0      # an open-loop answer may come this late


# --------------------------------------------------------------------------
# Records
# --------------------------------------------------------------------------

@dataclass
class Job:
    job_id: str
    samples: np.ndarray
    due_t: float | None = None          # open loop: when it was due
    start_t: float = 0.0
    end_t: float = 0.0
    first_submit_t: float | None = None
    deliveries: list = field(default_factory=list)  # (index, t, wait_s, cues)
    language_s: float = 0.0
    status: str = "running"             # ok | cut | failed | unfinished
    error: str = ""
    cues: list | None = None            # stitched, as the entry returned
    vtt: str | None = None
    stats: dict = field(default_factory=dict)
    handle: object = None
    gate: threading.Barrier | None = None   # ramp: submit together


class _Handle:
    """The job's side of the engine boundary: stamps submits and
    deliveries, keeps what was delivered."""

    def __init__(self, inner, job: Job, bus: "_Bus"):
        self._inner, self._job, self._bus = inner, job, bus

    def submit(self, index, start_s, samples):
        if self._job.first_submit_t is None:
            self._job.first_submit_t = time.monotonic()
        return self._inner.submit(index, start_s, samples)

    def results(self):
        for index, cues, wait_s in self._inner.results():
            t = time.monotonic()
            self._job.deliveries.append((index, t, wait_s, list(cues)))
            self._bus.delivered(t)
            yield index, cues, wait_s

    def drain_ready(self):
        return self._inner.drain_ready()

    def close(self):
        return self._inner.close()


class _JobEngine:
    """What ``transcribe_audio_engine`` sees as its engine: the real one,
    with the benchmark's spans around the two calls the entry makes."""

    def __init__(self, engine, job: Job, rec: Recorder, bus: "_Bus"):
        self._engine, self._job, self._rec, self._bus = engine, job, rec, bus

    def detect_language(self, samples):
        t0 = time.monotonic()
        with self._rec.span("language_pass", job=self._job.job_id):
            out = self._engine.detect_language(samples)
        self._job.language_s = time.monotonic() - t0
        if self._job.gate is not None:
            self._job.gate.wait(600.0)
        return out

    def begin_job(self, job, **kw):
        inner = self._engine.begin_job(job, **kw)
        self._job.handle = inner
        return _Handle(inner, self._job, self._bus)


class _Bus:
    """Delivery stamps of all jobs, so the closed loop can end its
    window on a tick boundary."""

    def __init__(self):
        self.cond = threading.Condition()
        self.stamps: list[float] = []

    def delivered(self, t: float) -> None:
        with self.cond:
            self.stamps.append(t)
            self.cond.notify_all()


# --------------------------------------------------------------------------
# Audio from the seed
# --------------------------------------------------------------------------

class Audio:
    """A tone (150 to 400 Hz, 0.25 of full scale) over a noise bed:
    ``chip_smoke.py``'s recipe (copied), which the job-side VAD gate
    passes in every window. Noise comes from a 30 s bank drawn once
    from the seed and rolled per recording, so no two windows are
    equal and set-up does not draw ten minutes of noise per client."""

    def __init__(self, seed: int):
        self.rng = np.random.default_rng([int(seed), 404])
        self.bank = (0.01 * self.rng.standard_normal(30 * SR)
                     ).astype(np.float32)

    def make(self, seconds: float) -> np.ndarray:
        n = int(round(seconds * SR))
        freq = self.rng.uniform(150.0, 400.0)
        roll = int(self.rng.integers(0, self.bank.size))
        t = np.arange(n, dtype=np.float64) / SR
        tone = (0.25 * np.sin(2.0 * np.pi * freq * t)).astype(np.float32)
        return tone + np.resize(np.roll(self.bank, roll), n)


def n_windows(seconds: float, window_s: float, stride_s: float) -> int:
    n, t = 1, 0.0
    while t + window_s < seconds:
        t += stride_s
        n += 1
    return n


def recording_s(windows: int, window_s: float, stride_s: float) -> float:
    return window_s + (windows - 1) * stride_s


# --------------------------------------------------------------------------
# The run
# --------------------------------------------------------------------------

def effective(spec: dict, rehearse: bool) -> dict:
    """A configuration or traffic file with its ``rehearsal`` overrides
    applied (one level deep) for the CPU rehearsal."""
    out = {k: v for k, v in spec.items() if k != "rehearsal"}
    if rehearse:
        for k, v in spec.get("rehearsal", {}).items():
            out[k] = {**out[k], **v} if isinstance(v, dict) \
                and isinstance(out.get(k), dict) else v
    return out


class Run:
    def __init__(self, cell, opts):
        self.cell, self.opts = cell, opts
        self.cfg = effective(cell.config, opts.rehearse)
        self.traffic = effective(cell.traffic, opts.rehearse)
        self.dep = self.cfg["deployment"]
        self.rec = Recorder()
        self.bus = _Bus()
        self.jobs: list[Job] = []
        self.jobs_lock = threading.Lock()
        self.closing = threading.Event()
        self.window_s = float(self.dep["window_s"])
        self.stride_s = self.window_s - float(self.dep["overlap_s"])
        self.beam = int(self.dep["env"]["VLOG_WHISPER_BEAM"])

    # ---- set-up ----------------------------------------------------------

    def build(self):
        self.parts = {"before_build_s": time.perf_counter()
                      - self.opts.t_start}
        for k, v in self.dep["env"].items():
            os.environ[k] = str(v)
        import jax

        apply_precision(self.dep)
        from models.whisper_weights import make_params
        from vlog_tpu import config
        from vlog_tpu.asr import decode, mel
        from vlog_tpu.asr.engine import AsrEngine

        want = {"ASR_BATCH_WINDOWS": int(self.dep["env"][
                    "VLOG_ASR_BATCH_WINDOWS"]),
                "WHISPER_BEAM": int(self.dep["env"]["VLOG_WHISPER_BEAM"]),
                "WHISPER_QUANT": self.dep["env"]["VLOG_WHISPER_QUANT"]}
        for k, v in want.items():
            if getattr(config, k) != v:
                raise RuntimeError(
                    f"vlog_tpu.config.{k} is {getattr(config, k)!r}, the "
                    f"configuration says {v!r}: the program was imported "
                    f"before the deployment's environment was set")
        self.jax = jax
        self.decode = decode
        t0 = time.monotonic()
        params = make_params(self.cfg, self.opts.seed)
        jax.block_until_ready(params)
        self.parts["weights_s"] = time.monotonic() - t0
        assets, self.vocab = build_assets(
            self.cfg, decode_quant(params,
                                   self.dep["env"]["VLOG_WHISPER_QUANT"]),
            name=self.cell.config_name)
        self.engine = AsrEngine(assets)
        self.steps = min(self.cfg["max_target_positions"] // 2,
                         self.cfg["max_target_positions"] - 3 - 1)

        def capture(args, kwargs, out):
            toks, nsp = out
            return {"toks": np.array(toks), "nsp": np.array(nsp),
                    "rows": int(args[1].shape[0]),
                    "beam": int(kwargs.get("beam", 1))}

        if not self.rec.wrap(decode, "generate_batch", "generate", capture):
            raise RuntimeError("vlog_tpu.asr.decode.generate_batch is gone: "
                               "this driver reads the model step there")
        self.rec.wrap(mel, "log_mel_spectrogram", "mel")
        self.rec.wrap(decode, "parse_segments", "parse")

    def run_job(self, job: Job) -> None:
        from vlog_tpu.asr.vtt import format_vtt
        from vlog_tpu.worker.transcribe import transcribe_audio_engine

        with self.jobs_lock:
            self.jobs.append(job)
        job.start_t = time.monotonic()
        try:
            cues, _lang, _n = transcribe_audio_engine(
                job.samples, _JobEngine(self.engine, job, self.rec, self.bus),
                job_key=job.job_id, stats_out=job.stats)
            job.vtt = format_vtt(cues)
            job.cues = [(c.start_s, c.end_s, c.text) for c in cues]
            job.end_t = time.monotonic()
            job.status = "ok"
        except Exception as e:  # noqa: BLE001 — a failed job is a count
            job.end_t = time.monotonic()
            job.status = "cut" if self.closing.is_set() else "failed"
            job.error = f"{type(e).__name__}: {e}"

    def warm_up(self, audio: Audio) -> None:
        """Open loop: one job per bucket the engine can form, through the
        entry, so that the language pass, the mel programs, each beam
        program and its cache page are compiled and resident before the
        window (the closed loop warms up by its own first tick)."""
        t0 = time.monotonic()
        full = int(self.dep["env"]["VLOG_ASR_BATCH_WINDOWS"])
        buckets = [b for b in (1, 2, 4, 8, 16, 32) if b < full] + [full]
        for rows in buckets:
            job = Job(f"warm-{rows}", audio.make(
                recording_s(rows, self.window_s, self.stride_s)))
            self.run_job(job)
            if job.status != "ok":
                raise RuntimeError(f"warm-up job failed: {job.error}")
        self.parts["warm_up_s"] = time.monotonic() - t0
        self.warm_jobs, self.jobs = self.jobs, []
        self.n_warm_ticks = len(self.engine.batch_log)
        self.rec.reset()
        self.bus.stamps.clear()

    # ---- traffic ---------------------------------------------------------

    def end_of_tick(self, after: float, limit: float) -> float | None:
        """Block until results land that are newer than ``after`` and
        return the last stamp of that burst (a tick delivers all its
        windows within milliseconds); ``None`` at ``limit``."""
        with self.bus.cond:
            while not any(s > after for s in self.bus.stamps):
                if time.monotonic() >= limit:
                    return None
                self.bus.cond.wait(0.05)
        time.sleep(SETTLE_S)
        with self.bus.cond:
            return self.bus.stamps[-1]

    def closed_loop(self, plan: dict, audio: Audio, tracer) -> dict:
        """Set-up runs the loop up to its steady state. All clients start
        together and their first jobs hold at a gate after the language
        pass, so that they submit together: a language pass that comes
        after the first tick has begun queues behind the beam program for
        8 to 19 s (my chip run, PR 25), and the queue would drain to a
        half-empty bucket meanwhile. The first tick is the warm-up
        (language pass, mel, the full bucket's beam program and its cache
        page); the window opens at its end and closes at the first tick
        boundary at or after ``--seconds``, so the rate is whole ticks
        over the time they took."""
        extra_s = 60.0
        bases = [audio.make(max(c["first_s"], c["then_s"]) + extra_s)
                 for c in plan["clients"]]
        firsts: list[Job] = []
        gate = threading.Barrier(len(plan["clients"]))

        def client(ci: int, c: dict):
            k = 0
            while not self.closing.is_set():
                n = int(round((c["first_s"] if k == 0 else c["then_s"]) * SR))
                off = (k * 7919 * 13) % int(extra_s * SR)
                job = Job(f"c{ci}-r{k}", bases[ci][off:off + n],
                          gate=gate if k == 0 else None)
                if k == 0:
                    firsts.append(job)
                self.run_job(job)
                k += 1

        threads = [threading.Thread(target=client, args=(i, c),
                                    name=f"bench-client-{i}", daemon=True)
                   for i, c in enumerate(plan["clients"])]
        ramp0 = time.monotonic()
        limit = ramp0 + 600.0
        # client 0's language pass goes first and alone: it loads the
        # pass's some 600 eager programs once, the others then find them
        threads[0].start()
        while not (firsts and firsts[0].language_s) \
                and time.monotonic() < limit:
            time.sleep(0.02)
        for t in threads[1:]:
            t.start()
        while not (len(firsts) == len(threads)
                   and all(j.first_submit_t for j in firsts)):
            if time.monotonic() >= limit or any(
                    j.status == "failed" for j in firsts):
                raise RuntimeError("the closed loop never reached its "
                                   "steady state: " + "; ".join(
                                       f"{j.job_id} {j.status} {j.error}"
                                       for j in firsts))
            time.sleep(0.02)
        t0 = self.end_of_tick(time.monotonic(), limit)
        if t0 is None:
            raise RuntimeError("no tick completed during the ramp")
        self.n_warm_ticks = len(self.engine.batch_log)
        self.parts["ramp_s"] = t0 - ramp0
        self.setup_done()
        self.trace_one_cycle(tracer, t0)
        time.sleep(max(0.0, t0 + self.opts.seconds - time.monotonic()))
        t_end = self.end_of_tick(time.monotonic(), time.monotonic() + 120.0)
        with self.bus.cond:
            n_done = sum(1 for s in self.bus.stamps
                         if t0 < s <= (t_end or 0.0))
        self.stop_traffic(threads)
        return {"t0": t0, "t_end": t_end or time.monotonic(),
                "windows_done": n_done,
                "audio_s_per_s": (n_done * self.stride_s / (t_end - t0)
                                  if n_done and t_end else None)}

    def open_loop(self, plan: dict, audio: Audio, tracer) -> dict:
        clips = [audio.make(j["audio_s"]) for j in plan["jobs"]]
        threads: list[threading.Thread] = []
        late: list[float] = []
        self.setup_done()
        t0 = time.monotonic() + 0.05
        tracer.trace_between(t0 + float(self.traffic["trace_start_s"]),
                             t0 + float(self.traffic["trace_start_s"])
                             + float(self.traffic["trace_seconds"]))
        for i, (j, samples) in enumerate(zip(plan["jobs"], clips)):
            due = t0 + j["due_s"]
            time.sleep(max(0.0, due - time.monotonic()))
            late.append(time.monotonic() - due)
            th = threading.Thread(
                target=self.run_job, name=f"bench-job-{i}", daemon=True,
                args=(Job(f"j{i}-b{j['burst']}", samples, due_t=due),))
            th.start()
            threads.append(th)
        time.sleep(max(0.0, t0 + self.opts.seconds - time.monotonic()))
        with self.jobs_lock:
            open_at_close = sum(1 for j in self.jobs if j.status == "running")
        limit = t0 + self.opts.seconds + LATE_WAIT_S
        for th in threads:
            th.join(max(0.0, limit - time.monotonic()))
        t_end = time.monotonic()
        with self.jobs_lock:
            for job in self.jobs:
                if job.status == "running":
                    job.status = "unfinished"
        self.stop_traffic(threads)
        ok = [j.end_t - j.due_t for j in self.jobs if j.status == "ok"]
        n_bad = sum(1 for j in self.jobs if j.status != "ok")
        return {"t0": t0, "t_end": t_end,
                "captions_p50_s": st.latency_percentile(ok, n_bad, 50),
                "captions_p90_s": st.latency_percentile(ok, n_bad, 90),
                "gen_jobs_open_at_close": open_at_close,
                "gen_drain_s": t_end - (t0 + self.opts.seconds),
                "gen_late_ms_max": 1000.0 * max(late, default=0.0),
                "gen_late_ms_mean": 1000.0 * (sum(late) / len(late)
                                              if late else 0.0)}

    def trace_one_cycle(self, tracer, t0: float) -> None:
        """Closed loop: trace from late in the window's second tick to the
        end of its third, so that one whole steady cycle of the engine
        (delivery, coalescing sleep, take, mel, the beam program, pull,
        parse) lies inside, and little more: a beam program writes 10^5
        device events a second and ``stop_trace`` needs 5 s for each."""
        if not tracer.enabled:
            return
        cap = float(self.traffic["trace_seconds"])

        def control():
            first = self.end_of_tick(t0, t0 + self.opts.seconds)
            if first is None:
                return
            # late in the second tick: the profiler is on before the third
            # begins and holds little of the second
            time.sleep(0.75 * (first - t0))
            tracer.start()
            self.rec.annotate = True
            started = time.monotonic()
            second = self.end_of_tick(started, started + cap)
            if second is not None:
                self.end_of_tick(second, started + cap)
            self.rec.annotate = False
            tracer.stop_now()

        threading.Thread(target=control, name="bench-trace-control",
                         daemon=True).start()

    def setup_done(self) -> None:
        self.parts["setup_s"] = time.perf_counter() - self.opts.t_start

    def stop_traffic(self, threads) -> None:
        self.closing.set()
        with self.jobs_lock:
            live = [j for j in self.jobs if j.status in ("running",
                                                         "unfinished")]
        for j in live:
            if j.handle is not None:
                j.handle.cancel()
        self.engine.close()
        for t in threads:
            t.join(10.0)

    # ---- after the window --------------------------------------------------

    def counters(self, t_end: float) -> dict:
        """``all_*``: every tick since the jobs of this window began (the
        check maps windows to rows through them); ``batch_log``: the
        ticks of the window alone (the readers' view)."""
        log = list(self.engine.batch_log)
        ticks = [s for s in self.rec.named("generate") if "toks" in s.data]
        keep = self.n_warm_ticks if self.mode == "closed" else 0
        all_log = log[self.n_warm_ticks - keep:]
        # a tick that the close caught in flight ends after the window
        inside = sum(1 for s in ticks[:len(all_log)] if s.t1 <= t_end)
        return {"all_log": all_log, "all_ticks": ticks,
                "batch_log": all_log[keep:max(keep, inside)],
                "engine_stats": self.engine.stats()}

    def free_program(self) -> None:
        self.rec.unwrap_all()
        self.decode.kv_pool.reset()
        self.engine.assets.params = {}
        self.engine = None
        self.jax.clear_caches()


def build_assets(cfg: dict, params, *, name: str):
    """The program's ``WhisperAssets`` over the benchmark's weights and
    tokenizer stand-in, and the reference's view of the same vocabulary
    (``configs/*.json`` ``vocab``: control ids at their published places,
    ONE language token)."""
    from reference import whisper_ref
    from vlog_tpu.asr.load import SpecialTokens, WhisperAssets
    from vlog_tpu.asr.model import WhisperConfig

    voc = cfg["vocab"]
    vocab = whisper_ref.Vocab(
        sot=voc["sot"], eot=voc["eot"], transcribe=voc["transcribe"],
        no_timestamps=voc["no_timestamps"],
        timestamp_begin=voc["timestamp_begin"], no_speech=voc["no_speech"],
        language=voc["language_id"], suppress=tuple(voc["suppress"]),
        begin_suppress=tuple(voc["begin_suppress"]))
    tokens = SpecialTokens(
        sot=voc["sot"], eot=voc["eot"], transcribe=voc["transcribe"],
        translate=voc["translate"], no_timestamps=voc["no_timestamps"],
        timestamp_begin=voc["timestamp_begin"], no_speech=voc["no_speech"],
        language_ids={voc["language"]: voc["language_id"]},
        suppress=vocab.suppress, begin_suppress=vocab.begin_suppress)

    class Tokenizer:
        decode = staticmethod(whisper_ref.decode_text)

    hf = {f: cfg[f] for f in (
        "d_model", "encoder_layers", "decoder_layers",
        "encoder_attention_heads", "decoder_attention_heads",
        "encoder_ffn_dim", "decoder_ffn_dim", "vocab_size", "num_mel_bins",
        "max_source_positions", "max_target_positions")}
    assets = WhisperAssets(cfg=WhisperConfig.from_hf(hf), params=params,
                           tokenizer=Tokenizer(), tokens=tokens,
                           model_name=name)
    return assets, vocab


def apply_precision(dep: dict) -> None:
    """The deployment's ``matmul_precision``: ``highest`` makes the
    program's float32 matmuls float32 on the TPU (JAX's own switch, no
    code of the program); ``default`` leaves JAX's default."""
    import jax

    want = dep.get("matmul_precision", "default")
    if want not in ("default", "high", "highest"):
        raise ValueError(f"matmul_precision {want!r}")
    jax.config.update("jax_default_matmul_precision",
                      None if want == "default" else want)


def decode_quant(params, mode: str):
    """``VLOG_WHISPER_QUANT`` as ``asr/load.py`` applies it when it reads
    a checkpoint; the driver builds the assets without the file loader,
    so it applies the program's own re-encoding here."""
    if mode in ("f32", "fp32", "", "none"):
        return params
    from vlog_tpu.asr.load import quantize_params

    return quantize_params(params, mode)


# --------------------------------------------------------------------------
# correct
# --------------------------------------------------------------------------

def check(run: Run, counters: dict) -> dict:
    """Compare what the window produced with the plain reference; see
    PERF.md "How correct is decided". Returns
    ``{name: {"value", "limit"}}``."""
    import jax.numpy as jnp

    from models.whisper_weights import make_params
    from reference import whisper_ref as ref

    chk = run.cfg["check"]
    vocab = run.vocab
    ticks, log = counters["all_ticks"], counters["all_log"]
    seen: dict[str, int] = {}
    served: dict[tuple[str, int], tuple[np.ndarray, float]] = {}
    for tick, entry in zip(ticks, log):
        for row, job_id in enumerate(entry["jobs"]):
            idx = seen.get(job_id, 0)
            seen[job_id] = idx + 1
            served[(job_id, idx)] = (tick.data["toks"][row],
                                     float(tick.data["nsp"][row]))
    by_id = {j.job_id: j for j in run.jobs}

    mismatched = 0
    compared_windows = 0
    for job in run.jobs:
        per_window: dict[int, list] = {}
        for index, _t, _wait, cues in job.deliveries:
            got = [(c.start_s, c.end_s, c.text) for c in cues]
            per_window[index] = got
            hit = served.get((job.job_id, index))
            if hit is None:
                mismatched += 1
                continue
            toks, nsp = hit
            want = [] if nsp > ref.NO_SPEECH_THRESHOLD else ref.parse_cues(
                toks.tolist(), vocab, start_s=index * run.stride_s,
                window_s=run.window_s)
            compared_windows += 1
            mismatched += got != want
        if job.status == "ok":
            n = n_windows(job.samples.size / SR, run.window_s, run.stride_s)
            if sorted(per_window) != list(range(n)) \
                    or job.stats.get("windows_live") != n:
                mismatched += 1
                continue
            want = ref.stitch([per_window[i] for i in range(n)])
            mismatched += (job.cues != want) + (job.vtt != ref.vtt(want))

    done = [j for j in run.jobs if j.status == "ok"]
    rng = np.random.default_rng([int(run.opts.seed), 505])
    picks: list[tuple[str, int]] = []
    if done:
        longest = max(done, key=lambda j: (len(j.deliveries), j.job_id))
        idxs = sorted(i for i, *_ in longest.deliveries)
        head = min(len(idxs), max(1, chk["windows"] // 3))
        picks += [(longest.job_id, int(i)) for i in
                  rng.choice(idxs, head, replace=False)]
        rest = [(j.job_id, i) for j in done if j is not longest
                for i, *_ in j.deliveries]
        more = min(len(rest), chk["windows"] - len(picks))
        picks += [rest[int(i)] for i in
                  rng.choice(len(rest), more, replace=False)] if more else []
    picks = [p for p in picks if p in served]

    gap, sq, n_tokens = 0.0, [], 0
    if picks:
        params = make_params(run.cfg, run.opts.seed)
        audio = np.stack([ref.pad_or_trim(
            by_id[j].samples[int(round(i * run.stride_s * SR)):])
            for j, i in picks])
        toks = np.stack([np.concatenate([vocab.prompt, served[p][0]])
                         for p in picks]).astype(np.int32)
        feats = ref.log_mel(jnp.asarray(audio),
                            n_mels=run.cfg["num_mel_bins"])
        lg = ref.logits(params, run.cfg, feats, toks,
                        block=chk.get("block", 4))
        for row, p in zip(lg, picks):
            gaps = ref.served_gaps(row, served[p][0], vocab,
                                   run.beam, rule_tol=chk["rule_tol"])
            n_tokens += len(gaps)
            gap = max(gap, max(gaps))
            sq.append((math.log(max(served[p][1], 1e-300))
                       - ref.no_speech_logp(row, vocab)) ** 2)
    never = sum(1 for j in run.jobs if j.status in ("failed", "unfinished"))
    return {
        "windows_logit_checked": {"value": len(picks),
                                  "limit": f">={chk['min_windows']}"},
        "served_tokens_checked": {"value": n_tokens, "limit": ">=1"},
        "beam_rank_gap": {"value": gap if math.isfinite(gap) else 1e30,
                          "limit": chk["beam_rank_gap"]},
        "nospeech_logp_err": {
            "value": math.sqrt(sum(sq) / len(sq)) if sq else 1e30,
            "limit": chk["nospeech_logp_err"]},
        "windows_cue_checked": {"value": compared_windows, "limit": ">=1"},
        "cue_mismatches": {"value": int(mismatched), "limit": 0},
        "unmatched_ticks": {"value": abs(len(ticks) - len(log)), "limit": 0},
        "jobs_failed_or_never_finished": {"value": never, "limit": 0},
    }


def verdict(compared: dict) -> bool:
    for c in compared.values():
        lim = c["limit"]
        if isinstance(lim, str):
            if not c["value"] >= float(lim[2:]):
                return False
        elif not c["value"] <= lim:
            return False
    return True


# --------------------------------------------------------------------------
# entry point of the driver
# --------------------------------------------------------------------------

def run(cell, opts, tracer) -> dict:
    from harness.spec import plugin

    r = Run(cell, opts)
    r.build()
    gen = plugin("generators", r.traffic["generator"])
    plan = gen.generate(r.traffic["params"], seed=opts.seed,
                        seconds=opts.seconds)
    audio = Audio(opts.seed)
    if plan["mode"] == "open":
        r.warm_up(audio)
    r.mode = plan["mode"]
    if plan["mode"] == "closed":
        window = r.closed_loop(plan, audio, tracer)
    else:
        window = r.open_loop(plan, audio, tracer)
    tracer.stop_now()
    mem = [d.memory_stats() or {} for d in r.jax.local_devices()[:cell.chips]]
    # what the allocator holds at the peak: live buffers plus the scratch
    # it reserves for the loaded programs' temporaries (PERF.md section 2)
    fullest = max(mem, key=lambda m: m.get("peak_bytes_in_use", 0)
                  + m.get("peak_bytes_reserved", 0))
    peak = (fullest.get("peak_bytes_in_use", 0)
            + fullest.get("peak_bytes_reserved", 0))
    counters = r.counters(window["t_end"])
    r.free_program()

    t0 = time.monotonic()
    compared = check(r, counters)
    check_s = time.monotonic() - t0

    jobs = r.jobs
    attempted = sum(1 for j in jobs if j.status != "cut")
    failed = sum(1 for j in jobs if j.status in ("failed", "unfinished"))
    e2e = {k: window.get(k) for k in ("audio_s_per_s", "captions_p50_s",
                                      "captions_p90_s")}
    e2e["setup_s"] = r.parts["setup_s"]
    cost = None
    if counters["batch_log"]:
        from models.whisper_costs import tick_cost

        cost = lambda n: tick_cost(  # noqa: E731
            r.cfg, windows=n, beams=r.beam, steps=r.steps)
    return {
        "correct": verdict(compared), "compared": compared,
        "attempted": attempted, "failed": failed,
        "end_to_end": e2e, "memory_peak_bytes": int(peak),
        "extra": {
            "window_s": window["t_end"] - window["t0"],
            "jobs": {s: sum(1 for j in jobs if j.status == s)
                     for s in ("ok", "cut", "failed", "unfinished")},
            "windows_done": window.get("windows_done", sum(
                len(j.deliveries) for j in jobs)),
            "ticks": len(counters["batch_log"]),
            "engine_stats": counters["engine_stats"],
            "memory_parts": {k: fullest.get(k) for k in (
                "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")},
            "tick_log": [[b["n"], b["rows"], round(b["elapsed_s"], 3)]
                         for b in counters["batch_log"]],
            "job_log": [[j.job_id, j.status, len(j.deliveries),
                         round(j.start_t - window["t0"], 2),
                         round((j.first_submit_t or j.start_t)
                               - window["t0"], 2),
                         round(j.end_t - window["t0"], 2),
                         round(j.language_s, 3)] for j in jobs][:80],
            "setup_parts_s": r.parts, "check_s": check_s,
            **{k: v for k, v in window.items() if k.startswith("gen_")},
            "offered_windows_per_s": plan.get("offered_windows_per_s"),
        },
        "layer_ctx": {
            "batch_log": counters["batch_log"],
            "jobs": jobs, "recorder": r.rec, "window": window,
            "tick_cost": cost, "stride_s": r.stride_s,
        },
    }
