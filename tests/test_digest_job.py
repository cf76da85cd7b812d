"""The digest job end to end through the daemon on a tiny seeded model
directory: transcription finished -> digest enqueued -> chapters.vtt and
digest.json beside captions.vtt, spans recorded, no failure row."""

# slowlane-ok(module): the tiny seeded model (hidden 64, six layers)
# builds its few step programs in seconds; the job end to end is tier-1's
import json

import numpy as np
import pytest

from lm_helpers import ByteTokenizer, geometry, save_model_dir, tiny

from vlog_tpu import config
from vlog_tpu.asr.vtt import Cue, format_vtt
from vlog_tpu.enums import JobKind
from vlog_tpu.jobs import claims, videos as vids
from vlog_tpu.jobs.finalize import finalize_transcription
from vlog_tpu.lm import engine as lm_engine
from vlog_tpu.worker import digest
from vlog_tpu.worker.daemon import WorkerDaemon


@pytest.fixture(autouse=True)
def _clean_plane(monkeypatch):
    # the engine's real geometry (pages of 256) does not fit a window
    # of 16: the tiny model is served at a tiny one
    monkeypatch.setattr(lm_engine, "default_geometry", lambda cfg: geometry(
        cfg, rows=4, chunk=64, page=16, cap=1024, block=4))
    lm_engine.reset_engine()
    yield
    lm_engine.reset_engine()


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    hf, _cfg, params = tiny()
    return save_model_dir(tmp_path_factory.mktemp("afmoe-tiny"), hf, params)


def test_vtt_round_trip_and_prompt_cut():
    cues = [Cue(0.0, 2.5, "hello <there> & you"), Cue(3661.25, 3663.0, "b")]
    back = digest.parse_vtt(format_vtt(cues))
    assert [(c.start_s, c.end_s, c.text) for c in back] == [
        (c.start_s, c.end_s, c.text) for c in cues]
    tok = ByteTokenizer()
    head = len(tok.encode(digest.INSTRUCTION))
    ids = digest.build_prompt(tok, back, room=head + 40)
    text = tok.decode(ids)
    assert text.startswith(digest.INSTRUCTION)
    assert text.endswith("[00:00:00] hello <there> & you\n")   # whole lines


def test_parse_digest_reads_chapters_and_falls_back():
    parsed = digest.parse_digest(
        "[00:00:00] Intro\n00:01:30 - Main part\n[09:00:00] too late\n"
        "Summary: one. two.\nTags: a, b ,c\n", end_s=600.0)
    assert parsed["chapters"] == [{"start_s": 0.0, "title": "Intro"},
                                  {"start_s": 90.0, "title": "Main part"}]
    assert parsed["summary"] == "one. two." and parsed["tags"] == [
        "a", "b", "c"]
    assert digest.parse_digest("\x00\x01 noise", end_s=10.0)["chapters"] \
        == [{"start_s": 0.0, "title": "Chapter 1"}]


def test_digest_job_through_the_daemon(run, db, tmp_path, model_dir,
                                       monkeypatch):
    monkeypatch.setattr(config, "DIGEST_DIR", str(model_dir))
    video = run(vids.create_video(db, "Digest me",
                                  source_path=str(tmp_path / "none.wav")))
    run(db.execute("UPDATE videos SET duration_s=120.0 WHERE id=:id",
                   {"id": video["id"]}))
    out_dir = tmp_path / "videos" / video["slug"]
    out_dir.mkdir(parents=True)
    cues = [Cue(10.0 * i, 10.0 * i + 8.0, f"caption number {i}")
            for i in range(12)]
    (out_dir / "captions.vtt").write_text(format_vtt(cues))
    # what the transcription job's completion does: with the knob set it
    # enqueues the digest
    run(finalize_transcription(db, video["id"], language="en", model="tiny",
                               vtt_path=str(out_dir / "captions.vtt"),
                               text="x"))
    job = run(db.fetch_one(
        "SELECT * FROM jobs WHERE video_id=:v AND kind='digest'",
        {"v": video["id"]}))
    assert job is not None

    daemon = WorkerDaemon(db, name="digest-1", video_dir=tmp_path / "videos",
                          kinds=(JobKind.DIGEST,))
    assert run(daemon.poll_once()) is True

    done = run(db.fetch_one("SELECT * FROM jobs WHERE id=:id",
                            {"id": job["id"]}))
    assert done["completed_at"] is not None and done["failed_at"] is None
    assert run(claims.get_failure_history(db, job["id"])) == []
    chapters = digest.parse_vtt((out_dir / "chapters.vtt").read_text())
    assert chapters and chapters[0].start_s == 0.0
    body = json.loads((out_dir / "digest.json").read_text())
    assert body["model"] == model_dir.name
    assert body["output_tokens"] >= 1 and body["prompt_tokens"] > len(
        digest.INSTRUCTION)
    assert body["chapters"][0]["title"]
    names = {r["name"] for r in run(db.fetch_all(
        "SELECT name FROM job_spans WHERE job_id=:j", {"j": job["id"]}))}
    assert {"worker.digest", "digest.job.prompt", "digest.job.served",
            "digest.job.write"} <= names
    eng = lm_engine.peek_engine()
    assert eng is not None and eng.stats()["requests_done"] == 1
    assert all(rec["phase_s"]["dispatch"] > 0 for rec in eng.step_log)
    assert not eng.active()


def test_no_digest_is_enqueued_without_the_knob(run, db, tmp_path,
                                               monkeypatch):
    monkeypatch.setattr(config, "DIGEST_DIR", "")
    video = run(vids.create_video(db, "Plain",
                                  source_path=str(tmp_path / "none.wav")))
    run(finalize_transcription(db, video["id"], language="en", model="tiny",
                               vtt_path=None, text="x"))
    assert run(db.fetch_one(
        "SELECT * FROM jobs WHERE video_id=:v AND kind='digest'",
        {"v": video["id"]})) is None


def test_digest_tokens_books_its_stages(model_dir):
    eng = lm_engine.get_engine(str(model_dir))
    stats = {}
    req = digest.digest_tokens(eng, np.arange(100) % 256, max_new=5,
                               job_key="t", capture=(0, -1),
                               stats_out=stats)
    assert len(req.tokens) == 5 and set(req.logits) == {0, 4}
    assert stats["prompt_tokens"] == 100 and stats["output_tokens"] == 5
    assert stats["prefill_steps"] == 2
    assert 0 <= stats["queue_s"] <= stats["first_token_s"] \
        <= stats["served_s"]


@pytest.mark.parametrize("missing", ["config.json", "tokenizer.json",
                                     "model.safetensors"])
def test_a_model_dir_that_lacks_a_file_is_refused(model_dir, tmp_path,
                                                  missing):
    """Above all the tokenizer: byte ids fed to the published vocabulary
    would give a digest of noise and a job that reports success."""
    import shutil

    from vlog_tpu.lm.load import LmLoadError, load_model_dir

    broken = tmp_path / "broken"
    shutil.copytree(model_dir, broken)
    (broken / missing).unlink()
    with pytest.raises(LmLoadError, match=missing.replace(".", r"\.")):
        load_model_dir(broken)


def test_two_jobs_claimed_together_load_the_weights_once(model_dir,
                                                        monkeypatch):
    import threading
    import time

    from vlog_tpu.lm import load

    loads = []
    real = load.load_model_dir

    def slow_load(path):
        loads.append(path)
        time.sleep(0.2)             # long enough for the second to arrive
        return real(path)

    monkeypatch.setattr(load, "load_model_dir", slow_load)
    got = []
    threads = [threading.Thread(
        target=lambda: got.append(lm_engine.get_engine(str(model_dir))))
        for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert len(loads) == 1 and len(got) == 2 and got[0] is got[1]
