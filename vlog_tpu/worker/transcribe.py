"""Transcription job: audio -> batched Whisper-JAX -> WebVTT.

Reference parity: worker/transcription.py:302-450 (process_transcription):
pick the audio source, extract 16 kHz mono PCM, run ASR, write
``captions.vtt`` next to the renditions, return language + full text.

TPU-shaped differences (SURVEY §5 long-audio plan): instead of
faster-whisper's sequential 30 s seek loop, the audio is cut into
overlapping 30 s windows up front and decoded in data-parallel batches
sharded over the device mesh — a 30-minute track is ~64 windows, i.e. a
handful of large dispatches. Digital-silence windows are skipped by an
energy gate before ever reaching the model (the VAD-filter analog,
reference transcription.py:105-111), and window outputs are stitched by
timestamp into one cue stream.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from vlog_tpu import config
from vlog_tpu.asr import mel as melmod
from vlog_tpu.asr.vtt import Cue, format_vtt, stitch_windows
from vlog_tpu.backends.base import ProgressFn
from vlog_tpu.obs import trace
from vlog_tpu.parallel import compile_cache


class TranscriptionUnavailable(RuntimeError):
    """No model weights configured (VLOG_WHISPER_DIR) — job should fail
    with a clear operator-actionable message."""


@dataclass
class TranscribeResult:
    language: str
    model: str
    vtt_path: str
    text: str
    cue_count: int
    windows: int


# RMS below this is digital silence — no model call needed.
SILENCE_RMS = 1e-4


def _cut_windows(samples: np.ndarray, *, window_s: float, overlap_s: float
                 ) -> list[tuple[float, np.ndarray]]:
    """(start_time, window_samples) list covering the track with overlap."""
    sr = melmod.SAMPLE_RATE
    win = int(window_s * sr)
    stride = int((window_s - overlap_s) * sr)
    n = samples.shape[-1]
    out = []
    t = 0
    while t < n:
        out.append((t / sr, samples[t:t + win]))
        if t + win >= n:
            break
        t += stride
    return out


def transcribe_audio_engine(
    samples: np.ndarray,
    engine,
    *,
    job_key: str,
    language: str | None = None,
    window_s: float | None = None,
    overlap_s: float | None = None,
    max_new: int | None = None,
    beam: int | None = None,
    progress_cb: ProgressFn | None = None,
    checkpoint_cb=None,
    resume: dict | None = None,
    stats_out: dict | None = None,
) -> tuple[list[Cue], str, int]:
    """Engine-backed transcription of one track: VAD-gate the windows
    here (job side), submit the live ones to the shared continuous-
    batching engine, and stream cue results back as batches complete.

    ``checkpoint_cb(state, done, total, final)`` fires after every
    completed window with the cumulative resume state — the caller
    persists it through the epoch-fenced ``jobs.last_checkpoint`` write
    (rate-limited; ``final=True`` is the drain-time flush and must not
    be dropped). ``resume`` is a prior attempt's state: its windows are
    restored verbatim and never re-submitted, so a resumed attempt
    decodes strictly fewer windows and still produces a byte-identical
    VTT (cue floats survive the JSON round-trip exactly).

    The entry records its own stages as spans (under the caller's
    ``worker.transcribe`` where there is one) and their seconds in
    ``stats_out``: ``asr.job.vad`` / ``vad_s`` (cut, speech spans, the
    gate), ``asr.job.language_pass`` / ``language_pass_s`` (with what
    the job's thread spent building the pass's eager programs as
    ``build.*_s`` attrs), ``asr.job.served`` / ``served_s`` (first
    submit to last result) and ``asr.job.stitch`` / ``stitch_s``. No
    span per window: a three-hour recording has 432.

    Returns (stitched cues, language, total window count).
    """
    from vlog_tpu.asr.vad import speech_spans, window_has_speech

    if stats_out is None:
        stats_out = {}
    window_s = window_s or config.WHISPER_CHUNK_S
    overlap_s = overlap_s if overlap_s is not None else config.WHISPER_OVERLAP_S
    with trace.span("asr.job.vad") as stage:
        windows = _cut_windows(samples, window_s=window_s,
                               overlap_s=overlap_s)
        spans = speech_spans(samples)
        live = [i for i, (t0, w) in enumerate(windows)
                if w.size and float(np.sqrt(np.mean(w ** 2))) > SILENCE_RMS
                and window_has_speech(spans, t0, t0 + window_s)]
    stats_out["vad_s"] = stage.duration_s
    per_window_cues: list[list[Cue]] = [[] for _ in windows]

    ckpt_windows: dict[str, list[list]] = {}
    resumed: set[int] = set()
    if resume and resume.get("v") == 1:
        language = language or resume.get("language") or None
        for idx_s, rows in (resume.get("windows") or {}).items():
            idx = int(idx_s)
            if 0 <= idx < len(windows):
                per_window_cues[idx] = [Cue(s, e, t) for s, e, t in rows]
                ckpt_windows[idx_s] = [list(r) for r in rows]
                resumed.add(idx)
        if resumed:
            try:
                from vlog_tpu.obs.metrics import runtime

                runtime().asr_windows.labels(result="resumed").inc(
                    len(resumed))
            except Exception:  # noqa: BLE001 — metrics never break the job
                pass
    to_submit = [i for i in live if i not in resumed]

    if language is None and not live:
        language = "en"
    if language is None:
        # The job's OWN first live window — co-batched jobs can never
        # pollute the language vote.
        built0 = compile_cache.thread_build_seconds()
        with trace.span("asr.job.language_pass") as stage:
            language = engine.detect_language(windows[live[0]][1])
            stage.attrs.update({
                f"build.{phase}_s": round(seconds - built0[phase], 4)
                for phase, seconds
                in compile_cache.thread_build_seconds().items()})
        stats_out["language_pass_s"] = stage.duration_s
        _observe_language_pass(stage.duration_s)

    handle = engine.begin_job(
        job_key, language=language, max_new=max_new,
        beam=config.WHISPER_BEAM if beam is None else beam)
    done = 0
    total = len(to_submit)
    waits: list[float] = []
    stats_out.update({"windows_total": len(windows),
                      "windows_live": len(live),
                      "windows_resumed": len(resumed),
                      "windows_submitted": total})

    def _record(index: int, cues: list[Cue]) -> None:
        per_window_cues[index] = list(cues)
        ckpt_windows[str(index)] = [[c.start_s, c.end_s, c.text]
                                    for c in cues]

    def _state() -> dict:
        return {"v": 1, "language": language, "windows": dict(ckpt_windows)}

    def _wait_stats() -> None:
        if waits:
            stats_out["queue_wait_mean_s"] = round(
                sum(waits) / len(waits), 4)
            stats_out["queue_wait_max_s"] = round(max(waits), 4)

    try:
        with trace.span("asr.job.served", windows=total) as stage:
            for i in to_submit:
                handle.submit(i, windows[i][0], windows[i][1])
            for index, cues, wait_s in handle.results():
                _record(index, cues)
                waits.append(wait_s)
                done += 1
                if checkpoint_cb:
                    checkpoint_cb(_state(), done, total, False)
                if progress_cb:
                    progress_cb(done, total,
                                f"transcribed {done}/{total} windows")
        stats_out["served_s"] = stage.duration_s
    except BaseException:
        # Drain flush: keep whatever the engine already decoded for this
        # job (the in-flight batch), then write one final checkpoint so
        # the successor attempt re-submits only what is truly missing.
        for index, cues, _wait_s in handle.drain_ready():
            _record(index, cues)
            done += 1
        if checkpoint_cb:
            try:
                checkpoint_cb(_state(), done, total, True)
            except Exception:  # noqa: BLE001 — the original abort wins
                pass
        _wait_stats()
        raise
    finally:
        handle.close()
    _wait_stats()
    with trace.span("asr.job.stitch") as stage:
        cues = stitch_windows(per_window_cues)
    stats_out["stitch_s"] = stage.duration_s
    return cues, language, len(windows)


def _observe_language_pass(seconds: float) -> None:
    try:
        from vlog_tpu.obs.metrics import runtime

        runtime().asr_language_pass.observe(seconds)
    except Exception:  # noqa: BLE001 — metrics never break the job
        pass


def transcribe_video(
    source_path: str | Path,
    out_dir: str | Path,
    *,
    model_dir: str | None = None,
    language: str | None = None,
    progress_cb: ProgressFn | None = None,
    max_new: int | None = None,
    engine=None,
    job_key: str | None = None,
    checkpoint_cb=None,
    resume: dict | None = None,
    stats_out: dict | None = None,
) -> TranscribeResult:
    """Full transcription job for one video (daemon handler entrypoint).

    Decoding goes through the process's shared continuous-batching
    engine (asr/engine.py): weights load once, windows from concurrent
    jobs pack into one batch, and the mesh is used via the scheduler's
    slot leases instead of an ad-hoc full-device grab.
    """
    from vlog_tpu.media.audio import extract_audio, resample, to_mono

    model_dir = model_dir or config.WHISPER_DIR or os.environ.get(
        "VLOG_WHISPER_DIR")
    if not model_dir or not Path(model_dir).exists():
        raise TranscriptionUnavailable(
            "no Whisper weights: set VLOG_WHISPER_DIR or pass --whisper-dir "
            "to a local HF-format model directory")
    if engine is None:
        from vlog_tpu.asr.engine import get_engine

        engine = get_engine(model_dir)

    audio = extract_audio(source_path)
    if audio is None or not audio.pcm.size:
        raise ValueError(f"{source_path}: no audio track to transcribe")
    audio = resample(to_mono(audio), melmod.SAMPLE_RATE)
    samples = np.ascontiguousarray(audio.pcm[0], np.float32)

    cues, lang, n_windows = transcribe_audio_engine(
        samples, engine, job_key=job_key or str(out_dir),
        language=language, max_new=max_new, progress_cb=progress_cb,
        checkpoint_cb=checkpoint_cb, resume=resume, stats_out=stats_out)

    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    vtt_path = out_dir / "captions.vtt"
    tmp = vtt_path.with_suffix(".vtt.tmp")
    tmp.write_text(format_vtt(cues))
    tmp.rename(vtt_path)
    return TranscribeResult(
        language=lang, model=engine.assets.model_name,
        vtt_path=str(vtt_path), text=" ".join(c.text for c in cues),
        cue_count=len(cues), windows=n_windows)
