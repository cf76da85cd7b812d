"""Transcription pipeline: windows, stitching, VTT, and the daemon job.

Reference analog: the transcription worker tests — audio in, correctly
timed captions.vtt out, DB rows updated. Model quality is covered by the
torch-oracle tests (test_whisper.py); these tests prove the pipeline.
"""

from __future__ import annotations

import numpy as np
import pytest

pytest.importorskip("torch")
pytest.importorskip("transformers")

from vlog_tpu.asr.vtt import Cue, format_vtt, stitch_windows
from vlog_tpu.enums import JobKind
from vlog_tpu.jobs import claims, videos as vids
from vlog_tpu.media.audio import AudioData, write_wav
from vlog_tpu.worker.transcribe import (
    TranscriptionUnavailable,
    _cut_windows,
    transcribe_audio_engine,
    transcribe_video,
)


# --------------------------------------------------------------------------
# VTT / stitching units
# --------------------------------------------------------------------------

def test_format_vtt():
    out = format_vtt([Cue(0.0, 2.5, "hello"), Cue(3661.25, 3662.0, "world")])
    assert out.startswith("WEBVTT\n\n")
    assert "00:00:00.000 --> 00:00:02.500\nhello" in out
    assert "01:01:01.250 --> 01:01:02.000\nworld" in out


def test_format_vtt_skips_empty_cues():
    out = format_vtt([Cue(0, 1, "  "), Cue(1, 2, "ok")])
    assert out.count("-->") == 1


def test_stitch_drops_overlap_duplicates():
    w0 = [Cue(0.0, 10.0, "a"), Cue(10.0, 28.0, "b")]
    w1 = [Cue(26.0, 27.5, "b tail dup"), Cue(29.0, 40.0, "c")]
    cues = stitch_windows([w0, w1])
    assert [c.text for c in cues] == ["a", "b", "c"]
    assert cues[2].start_s == 29.0


def test_stitch_clamps_partial_overlap():
    w0 = [Cue(0.0, 28.0, "a")]
    w1 = [Cue(26.0, 33.0, "b")]
    cues = stitch_windows([w0, w1])
    assert cues[1].start_s == 28.0   # clamped to emitted_until
    assert cues[1].end_s == 33.0


def test_cut_windows_cover_and_overlap():
    sr = 16000
    samples = np.zeros(int(70 * sr), np.float32)
    wins = _cut_windows(samples, window_s=30.0, overlap_s=5.0)
    starts = [t for t, _ in wins]
    assert starts == [0.0, 25.0, 50.0]
    assert wins[-1][1].shape[-1] == 20 * sr
    # short track: one window
    wins = _cut_windows(np.zeros(sr, np.float32), window_s=30.0, overlap_s=5.0)
    assert len(wins) == 1
    # zero-length input: no windows at all (the old loop emitted one
    # empty window that wasted a batch row downstream)
    assert _cut_windows(np.zeros(0, np.float32),
                        window_s=30.0, overlap_s=5.0) == []


# --------------------------------------------------------------------------
# Pipeline with the tiny oracle model
# --------------------------------------------------------------------------

def _tone(duration_s: float, sr: int = 16000) -> np.ndarray:
    t = np.arange(int(duration_s * sr)) / sr
    return (0.25 * np.sin(2 * np.pi * 220 * t)).astype(np.float32)


@pytest.fixture(scope="session")
def assets(tiny_model_dir):
    from vlog_tpu.asr.load import load_whisper

    return load_whisper(tiny_model_dir)


def _through_engine(assets, samples, **kw):
    """One job through an engine of its own; (cues, language, windows)
    and the engine's tick records."""
    from vlog_tpu.asr.engine import AsrEngine

    engine = AsrEngine(assets, batch_windows=8, tick_s=0.05)
    try:
        out = transcribe_audio_engine(samples, engine, job_key="t",
                                      language="en", **kw)
    finally:
        engine.close()
    return out, engine.batch_log


@pytest.mark.slow  # ~10s multi-window decode; single-window tests stay fast
def test_transcribe_audio_batches_and_stitches(assets):
    samples = _tone(40.0)     # 2 windows at 25 s stride
    calls = []
    (cues, lang, windows), log = _through_engine(
        assets, samples, max_new=8,
        progress_cb=lambda d, t, m: calls.append((d, t)))
    assert lang == "en" and windows == 2
    assert calls[-1][0] == calls[-1][1] == 2
    assert sum(b["n"] for b in log) == 2      # cut, batched, stitched
    for c in cues:
        assert 0.0 <= c.start_s <= c.end_s <= 60.0


def test_silence_skips_model(assets):
    samples = np.zeros(16000 * 35, np.float32)
    (cues, _lang, windows), log = _through_engine(assets, samples,
                                                  max_new=4)
    assert cues == [] and windows == 2
    assert log == []            # no window reached the model


def test_transcribe_video_writes_vtt(tmp_path, tiny_model_dir, assets):
    wav = tmp_path / "a.wav"
    write_wav(wav, AudioData(pcm=_tone(8.0)[None].astype(np.float64),
                             sample_rate=16000))
    res = transcribe_video(wav, tmp_path / "out",
                           model_dir=str(tiny_model_dir), language="en",
                           max_new=8)
    assert res.language == "en"
    assert res.windows == 1
    vtt = (tmp_path / "out" / "captions.vtt").read_text()
    assert vtt.startswith("WEBVTT")
    assert not list((tmp_path / "out").glob("*.tmp"))


def test_transcribe_video_reuses_process_engine(tmp_path, tiny_model_dir):
    """Two transcriptions in one process share one engine (weights load
    once through the memoized load_whisper)."""
    from vlog_tpu.asr.engine import peek_engine, reset_engine

    reset_engine()
    try:
        for name in ("a", "b"):
            wav = tmp_path / f"{name}.wav"
            write_wav(wav, AudioData(pcm=_tone(4.0)[None].astype(np.float64),
                                     sample_rate=16000))
            transcribe_video(wav, tmp_path / f"out-{name}",
                             model_dir=str(tiny_model_dir), language="en",
                             max_new=8)
            if name == "a":
                first = peek_engine()
                assert first is not None
        assert peek_engine() is first
        assert peek_engine().windows_decoded == 2
    finally:
        reset_engine()


def test_missing_model_dir_raises_actionable_error(tmp_path):
    with pytest.raises(TranscriptionUnavailable, match="VLOG_WHISPER_DIR"):
        transcribe_video(tmp_path / "a.wav", tmp_path / "out",
                         model_dir=str(tmp_path / "nope"))


# --------------------------------------------------------------------------
# Daemon integration: the transcription job kind
# --------------------------------------------------------------------------

@pytest.mark.slow  # ~14s daemon e2e; direct transcription tests stay fast
def test_daemon_transcription_job(run, db, tmp_path, tiny_model_dir):
    from vlog_tpu.worker.daemon import WorkerDaemon

    wav = tmp_path / "talk.wav"
    write_wav(wav, AudioData(pcm=_tone(6.0)[None].astype(np.float64),
                             sample_rate=16000))
    video = run(vids.create_video(db, "Talk", source_path=str(wav)))
    run(db.execute("UPDATE videos SET duration_s=6.0 WHERE id=:id",
                   {"id": video["id"]}))
    run(claims.enqueue_job(db, video["id"], JobKind.TRANSCRIPTION))
    daemon = WorkerDaemon(db, name="tw", video_dir=tmp_path / "videos",
                          progress_min_interval_s=0.0,
                          transcription_model_dir=str(tiny_model_dir))
    run(daemon.poll_once())

    tr = run(db.fetch_one("SELECT * FROM transcriptions WHERE video_id=:v",
                          {"v": video["id"]}))
    assert tr is not None and tr["status"] == "completed"
    assert tr["language"] == "en"
    row = run(vids.get_video(db, video["id"]))
    assert row["transcription_status"] == "completed"
    job = run(db.fetch_one("SELECT * FROM jobs WHERE video_id=:v",
                           {"v": video["id"]}))
    assert job["completed_at"] is not None


def test_daemon_transcription_fails_without_weights(run, db, tmp_path):
    from vlog_tpu.worker.daemon import WorkerDaemon

    wav = tmp_path / "talk.wav"
    write_wav(wav, AudioData(pcm=_tone(2.0)[None].astype(np.float64),
                             sample_rate=16000))
    video = run(vids.create_video(db, "NoModel", source_path=str(wav)))
    run(claims.enqueue_job(db, video["id"], JobKind.TRANSCRIPTION,
                           max_attempts=1))
    daemon = WorkerDaemon(db, name="tw", video_dir=tmp_path / "videos",
                          progress_min_interval_s=0.0,
                          transcription_model_dir=str(tmp_path / "missing"))
    run(daemon.poll_once())
    row = run(vids.get_video(db, video["id"]))
    assert row["transcription_status"] == "failed"
    job = run(db.fetch_one("SELECT * FROM jobs WHERE video_id=:v",
                           {"v": video["id"]}))
    assert job["failed_at"] is not None
    assert "VLOG_WHISPER_DIR" in job["error"]