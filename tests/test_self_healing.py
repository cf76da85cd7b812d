"""Self-healing mesh: device-fault quarantine, epoch-fenced claim
writes, and coordination-plane brownout (ISSUE 7 chaos suite).

Three fault classes, each contained at its own blast radius:

- a sick chip quarantines its slot's devices, the partition
  renegotiates around the hole, and the victim job requeues as
  ``device_fault`` WITHOUT burning its attempt budget — the retry's
  tree is byte-identical to an untouched run (the PR-6 width-invariance
  carried through the renegotiated mesh);
- a partitioned worker whose lease was swept and re-claimed — under the
  SAME worker name, where ownership checks cannot tell incarnations
  apart — gets 409 on every stale-epoch write (``X-Claim-Epoch``
  fencing) while the successor publishes a clean, manifest-verified
  tree;
- a flapping database paces the claim loop onto jittered backoff
  behind the brownout breaker (readiness degrades, ingestion pauses)
  while the delivery plane keeps serving stale publish state.
"""

from __future__ import annotations

import asyncio

import pytest

from vlog_tpu import config
from vlog_tpu.enums import AcceleratorKind, FailureClass, JobKind
from vlog_tpu.jobs import claims, state as js, videos as vids
from vlog_tpu.parallel import faults
from vlog_tpu.parallel.scheduler import MeshScheduler
from vlog_tpu.utils import failpoints
from vlog_tpu.worker.brownout import CoordinationBreaker
from vlog_tpu.worker.daemon import WorkerDaemon
from tests.fixtures.media import make_y4m


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


def make_daemon(db, tmp_path, **kw):
    kw.setdefault("name", "heal-worker")
    kw.setdefault("accelerator", AcceleratorKind.TPU)
    kw.setdefault("video_dir", tmp_path / "videos")
    kw.setdefault("progress_min_interval_s", 0.0)
    return WorkerDaemon(db, **kw)


# --------------------------------------------------------------------------
# Device-fault classification (parallel/faults.py)
# --------------------------------------------------------------------------

class TestClassification:
    def test_synthetic_fault_classifies(self):
        assert faults.is_device_fault(faults.SyntheticDeviceFault("boom"))

    def test_xla_like_type_names_classify(self):
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        assert faults.is_device_fault(XlaRuntimeError("whatever"))

    def test_runtime_message_shapes_classify(self):
        assert faults.is_device_fault(RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "1073741824 bytes"))
        assert faults.is_device_fault(RuntimeError(
            "INTERNAL: Failed to execute XLA Runtime executable"))

    def test_input_and_codec_errors_do_not_classify(self):
        assert not faults.is_device_fault(ValueError("bad y4m header"))
        assert not faults.is_device_fault(OSError("no such file: device.mp4"))
        # a RuntimeError about the INPUT must not classify either
        assert not faults.is_device_fault(RuntimeError("bad payload"))
        # other armed failpoints are plumbing faults, not device faults
        assert not faults.is_device_fault(
            failpoints.FailpointError("claims.complete"))

    def test_wrapped_device_fault_classifies_through_cause(self):
        try:
            try:
                raise faults.SyntheticDeviceFault("halted")
            except faults.SyntheticDeviceFault as inner:
                raise RuntimeError("pipeline stage failed") from inner
        except RuntimeError as exc:
            assert faults.is_device_fault(exc)


# --------------------------------------------------------------------------
# Scheduler quarantine units (string devices — no JAX needed)
# --------------------------------------------------------------------------

def _sched(n=8, slots=2):
    return MeshScheduler(devices=[f"d{i}" for i in range(n)], slots=slots)


class TestQuarantine:
    def test_fault_quarantines_slot_and_renegotiates_widths(self):
        s = _sched(8, slots=2)
        t = s.admit()
        other = s.admit()
        lease = t.acquire()            # slot 0, width 4
        assert lease.width == 4
        newly = s.report_device_fault(lease)
        assert len(newly) == 4
        # sick slot stops granting immediately; the healthy one still does
        lease.release()
        t.close()
        got = other.acquire(timeout=1.0)
        assert all(d not in newly for d in got.devices)
        got.release()
        other.close()
        # job boundary: partition renegotiates around the hole
        assert s.capacity() == 2
        snap = s.snapshot()
        assert snap["healthy"] == 4 and snap["quarantined"] == 4
        assert snap["slots"] == 2 and snap["slot_width"] == 2

    def test_probe_reinstates_healed_devices(self):
        s = _sched(8, slots=2)
        t = s.admit()
        lease = t.acquire()
        s.report_device_fault(lease)
        lease.release()
        t.close()
        sick = set(lease.devices)
        # heal half: only passing devices rejoin
        results = s.probe_quarantined(
            probe_fn=lambda d: d in (lease.devices[0], lease.devices[1]))
        assert sum(results.values()) == 2
        assert s.snapshot()["quarantined"] == len(sick) - 2
        # heal the rest
        s.probe_quarantined(probe_fn=lambda d: True)
        snap = s.snapshot()
        assert snap["quarantined"] == 0 and snap["healthy"] == 8
        assert snap["slots"] == 2 and snap["slot_width"] == 4

    def test_raising_probe_counts_as_failing(self):
        s = _sched(4, slots=2)
        t = s.admit()
        lease = t.acquire()
        s.report_device_fault(lease)
        lease.release()
        t.close()

        def bad_probe(d):
            raise RuntimeError("probe dispatch failed")

        results = s.probe_quarantined(probe_fn=bad_probe)
        assert results and not any(results.values())
        assert s.snapshot()["quarantined"] == len(lease.devices)

    def test_threshold_gates_quarantine(self, monkeypatch):
        monkeypatch.setattr(config, "QUARANTINE_THRESHOLD", 2)
        s = _sched(4, slots=2)
        t = s.admit()
        lease = t.acquire()
        assert s.report_device_fault(lease) == ()     # 1 of 2 strikes
        assert s.snapshot()["quarantined"] == 0
        assert len(s.report_device_fault(lease)) == len(lease.devices)
        lease.release()
        t.close()

    def test_all_devices_quarantined_blocks_grants_until_heal(self):
        s = _sched(4, slots=1)
        t = s.admit()
        lease = t.acquire()            # full mesh (slots=1)
        s.report_device_fault(lease)
        lease.release()
        t.close()
        assert s.capacity() == 0
        late = s.admit()
        with pytest.raises(TimeoutError):
            late.acquire(timeout=0.1)
        late.close()
        s.probe_quarantined(probe_fn=lambda d: True)
        assert s.capacity() == 1
        again = s.admit()
        healed = again.acquire(timeout=1.0)
        assert healed.width == 4
        healed.release()
        again.close()

    def test_quarantine_metrics_rendered(self):
        from vlog_tpu.obs.metrics import HAVE_PROMETHEUS, runtime

        s = _sched(4, slots=2)
        t = s.admit()
        lease = t.acquire()
        s.report_device_fault(lease)
        lease.release()
        t.close()
        s.probe_quarantined(probe_fn=lambda d: True)
        if HAVE_PROMETHEUS:
            text = runtime().render_text()
            assert "vlog_slot_quarantined_total" in text
            assert 'vlog_device_probe_total{outcome="pass"}' in text
            assert "vlog_device_quarantined 0.0" in text


# --------------------------------------------------------------------------
# fail_job: device_fault refunds the attempt budget
# --------------------------------------------------------------------------

def test_device_fault_refunds_attempt_budget_with_bound(run, db, tmp_path):
    src = make_y4m(tmp_path / "s.y4m", n_frames=6, width=64, height=48)
    video = run(vids.create_video(db, "Innocent", source_path=str(src)))
    job_id = run(claims.enqueue_job(db, video["id"], max_attempts=2))

    async def go():
        # an innocent job's device-fault attempts are refunded...
        for _ in range(2):
            job = await claims.claim_job(db, "w1")
            assert job is not None and job["id"] == job_id
            row = await claims.fail_job(
                db, job_id, "w1", "device halted",
                failure_class=FailureClass.DEVICE_FAULT)
            assert row["attempt"] == 0          # refunded
            assert row["failed_at"] is None     # not terminal
            assert row["next_retry_at"] is None  # no backoff: requeue now
        # ...but only max_attempts times: a "device fault" that follows
        # the job across devices (deterministic HBM OOM, poison input)
        # starts burning budget instead of livelocking forever
        job = await claims.claim_job(db, "w1")
        row = await claims.fail_job(
            db, job_id, "w1", "device halted",
            failure_class=FailureClass.DEVICE_FAULT)
        assert row["attempt"] == 1              # bound hit: charged
        assert row["failed_at"] is None
        assert row["next_retry_at"] is not None  # transient-style backoff
        await db.execute(
            "UPDATE jobs SET next_retry_at=NULL WHERE id=:i", {"i": job_id})
        job = await claims.claim_job(db, "w1")
        row = await claims.fail_job(
            db, job_id, "w1", "device halted",
            failure_class=FailureClass.DEVICE_FAULT)
        assert row["failed_at"] is not None      # dead-lettered, finally
        history = await claims.get_failure_history(db, job_id)
        assert len(history) == 4
        assert {h["failure_class"] for h in history} == {"device_fault"}

    run(go())


# --------------------------------------------------------------------------
# A device-classified error that repeats on healthy devices is the job's
# (PR 21: what a compile-time HBM OOM of the ladder program looks like)
# --------------------------------------------------------------------------

# the shape jax 0.9.0 raises on a TPU v5 lite when a program's buffers
# exceed HBM (chip run, PR 21); the class name classifies as a device
# fault, the failure is a property of the program
_HBM_OOM = ("RESOURCE_EXHAUSTED: Allocation (size={size}) would exceed "
            "memory (size=17179869184) :: #allocation5 [shape = "
            "'f32[100000,100000]{{0,1:T(8,128)}}', space=hbm, size = "
            "0x{addr:x}, tag = 'output of multiply_add_fusion@{{}}']")


class JaxRuntimeError(RuntimeError):
    """Same NAME as jax.errors.JaxRuntimeError (faults matches names)."""


class TestRepeatFaultDetector:
    def test_first_sight_is_hardware_repeat_on_healthy_is_the_job(self):
        probed = []
        det = faults.RepeatFaultDetector(
            probe=lambda d: probed.append(d) or True)
        exc = JaxRuntimeError(_HBM_OOM.format(size=40038400000, addr=255))
        assert faults.is_device_fault(exc)
        assert det.repeats_on_healthy_devices(7, exc, ("d0", "d1")) is False
        assert probed == []          # nothing to compare with yet
        # same failure, different volatile numbers
        again = JaxRuntimeError(_HBM_OOM.format(size=40038400001, addr=4095))
        assert det.repeats_on_healthy_devices(7, again, ("d0", "d1")) is True
        assert probed == ["d0", "d1"]

    def test_other_job_or_other_error_is_not_a_repeat(self):
        det = faults.RepeatFaultDetector(probe=lambda d: True)
        exc = JaxRuntimeError(_HBM_OOM.format(size=1, addr=1))
        assert det.repeats_on_healthy_devices(1, exc, ("d",)) is False
        assert det.repeats_on_healthy_devices(2, exc, ("d",)) is False
        other = JaxRuntimeError("INTERNAL: device halted")
        assert det.repeats_on_healthy_devices(1, other, ("d",)) is False
        # ...and the error that replaced it is what a repeat compares to
        assert det.repeats_on_healthy_devices(1, exc, ("d",)) is False

    def test_failing_or_raising_probe_keeps_the_hardware_suspect(self):
        exc = JaxRuntimeError("INTERNAL: device halted")
        det = faults.RepeatFaultDetector(probe=lambda d: False)
        det.repeats_on_healthy_devices(1, exc, ("d",))
        assert det.repeats_on_healthy_devices(1, exc, ("d",)) is False

        def boom(_d):
            raise RuntimeError("probe could not allocate")

        det = faults.RepeatFaultDetector(probe=boom)
        det.repeats_on_healthy_devices(1, exc, ("d",))
        assert det.repeats_on_healthy_devices(1, exc, ("d",)) is False

    def test_memory_is_bounded(self):
        det = faults.RepeatFaultDetector(probe=lambda d: True, capacity=4)
        exc = JaxRuntimeError("INTERNAL: device halted")
        for job in range(10):
            det.repeats_on_healthy_devices(job, exc, ())
        assert len(det._seen) == 4


def test_repeated_device_error_fails_the_job_permanently(run, db, tmp_path,
                                                         monkeypatch):
    """Daemon loop on one healthy (virtual) device set: the first
    device-classified failure is refunded as ``device_fault``; the SAME
    failure on the retry, with the real put/reduce/pull probe passing,
    dead-letters the job as ``permanent`` — a waiter gets the error
    after two attempts instead of a refund loop."""
    src = make_y4m(tmp_path / "src.y4m", n_frames=4, width=64, height=48,
                   fps=24)
    v = run(vids.create_video(db, "Too big", source_path=str(src)))
    jid = run(claims.enqueue_job(db, v["id"]))

    def too_big(*_a, **_k):
        raise JaxRuntimeError(_HBM_OOM.format(size=40038400000, addr=255))

    monkeypatch.setattr("vlog_tpu.worker.pipeline.process_video", too_big)
    daemon = make_daemon(db, tmp_path)

    assert run(daemon.poll_once()) is True
    row = run(db.fetch_one("SELECT * FROM jobs WHERE id=:id", {"id": jid}))
    assert row["failed_at"] is None and row["attempt"] == 0   # refunded
    assert run(daemon.poll_once()) is True
    row = run(db.fetch_one("SELECT * FROM jobs WHERE id=:id", {"id": jid}))
    assert row["failed_at"] is not None
    assert "not a hardware fault" in row["error"]
    history = run(claims.get_failure_history(db, jid))
    assert [h["failure_class"] for h in history] == ["device_fault",
                                                     "permanent"]
    video = run(vids.get_video(db, v["id"]))
    assert video["status"] == "failed"
    assert run(daemon.poll_once()) is False      # nothing left to claim


# --------------------------------------------------------------------------
# The full chaos loop: fault mid-job -> quarantine -> renegotiate ->
# refund-requeue -> byte-identical retry (ISSUE 7 acceptance)
# --------------------------------------------------------------------------

@pytest.mark.slow  # ~30s chaos loop; the targeted fault-path tests stay fast
def test_device_fault_chaos_full_loop(run, db, tmp_path):
    import jax

    from vlog_tpu.storage import integrity

    # two videos with IDENTICAL source bytes: the survivor's tree is the
    # byte-identity reference for the faulted job's retry (slot widths
    # differ across the renegotiation — the PR-6 invariant covers that)
    blob = make_y4m(tmp_path / "src0.y4m", n_frames=8, width=128,
                    height=96, fps=24)
    src1 = tmp_path / "src1.y4m"
    src1.write_bytes(blob.read_bytes())
    videos, job_ids = [], []
    for i, src in enumerate((blob, src1)):
        v = run(vids.create_video(db, f"Chaos {i}", source_path=str(src)))
        job_ids.append(run(claims.enqueue_job(db, v["id"])))
        videos.append(v)

    sched = MeshScheduler(devices=list(jax.devices()), slots=2)
    daemon = make_daemon(db, tmp_path, scheduler=sched)
    failpoints.arm("device.fault", count=1)

    async def round_one():
        assert await daemon._poll_fill() is True
        assert len(daemon._tasks) == 2
        await asyncio.gather(*daemon._tasks)

    run(round_one())

    # exactly one job took the injected fault and was requeued as
    # device_fault with its attempt refunded; the other completed
    outcomes = {}
    for v, jid in zip(videos, job_ids):
        row = run(db.fetch_one("SELECT * FROM jobs WHERE id=:id",
                               {"id": jid}))
        outcomes[jid] = row
    faulted = [j for j, r in outcomes.items() if r["completed_at"] is None]
    done = [j for j, r in outcomes.items() if r["completed_at"] is not None]
    assert len(faulted) == 1 and len(done) == 1
    fj = outcomes[faulted[0]]
    assert fj["attempt"] == 0, "device fault must refund the attempt"
    assert fj["failed_at"] is None and fj["next_retry_at"] is None
    history = run(claims.get_failure_history(db, faulted[0]))
    assert [h["failure_class"] for h in history] == ["device_fault"]
    # the injected fault is the hardware's problem, not compute health:
    # the breaker must not have tripped toward open
    assert daemon.breaker.consecutive_failures == 0

    # the faulting slot's devices are quarantined and the partition
    # renegotiated around the hole at the job boundary
    assert sched.quarantined_count() == 4
    snap = sched.snapshot()
    assert snap["healthy"] == 4
    assert snap["slots"] == 2 and snap["slot_width"] == 2

    async def round_two():
        assert await daemon._poll_fill() is True
        await asyncio.gather(*daemon._tasks)

    run(round_two())
    retried = run(db.fetch_one("SELECT * FROM jobs WHERE id=:id",
                               {"id": faulted[0]}))
    assert retried["completed_at"] is not None, retried["error"]
    assert retried["attempt"] == 1     # one attempt spent, total

    # byte-identical, manifest-verified trees: the retried tree (on the
    # renegotiated healthy mesh) matches the survivor's untouched tree
    trees = {jid: tmp_path / "videos" / v["slug"]
             for v, jid in zip(videos, job_ids)}
    manifests = {}
    for jid, root in trees.items():
        manifest = integrity.load_manifest(root)
        assert manifest is not None
        assert integrity.verify_tree(root, manifest) == []
        manifests[jid] = {rel: meta["sha256"]
                          for rel, meta in manifest.items()
                          if not rel.startswith("original")}
    assert manifests[faulted[0]] == manifests[done[0]]

    # probe heals: the full mesh is back for the next job
    sched.probe_quarantined(probe_fn=lambda d: True)
    assert sched.snapshot()["healthy"] == 8
    assert sched.capacity() == 2


# --------------------------------------------------------------------------
# Epoch fencing over HTTP (swept-then-reclaimed, same worker name)
# --------------------------------------------------------------------------

@pytest.fixture
def api(run, db, tmp_path):
    from aiohttp.test_utils import TestServer

    from vlog_tpu.api.worker_api import build_worker_app
    from vlog_tpu.worker.remote import WorkerAPIClient

    video_dir = tmp_path / "srv-videos"
    app = build_worker_app(db, video_dir=video_dir)
    server = TestServer(app)
    run(server.start_server())
    base = str(server.make_url(""))
    key = run(WorkerAPIClient.register(base, "rw1", accelerator="tpu"))
    client = WorkerAPIClient(base, key, timeout=30.0, retries=0)
    yield {"base": base, "key": key, "client": client,
           "video_dir": video_dir, "db": db, "server": server}
    run(client.aclose())
    run(server.close())


@pytest.mark.slow  # ~25s sweep+reclaim end-to-end
def test_stale_epoch_writes_rejected_after_sweep_and_reclaim(
        run, db, tmp_path, api):
    """The fencing acceptance: worker A's lease is swept and the job
    re-claimed under the SAME worker name. Ownership checks cannot tell
    the incarnations apart — only the epoch can, and every stale write
    must bounce with 409 while the successor publishes clean."""
    from vlog_tpu.storage import integrity
    from vlog_tpu.worker.remote import ClaimLost, RemoteWorker, \
        WorkerAPIClient

    src = make_y4m(tmp_path / "f.y4m", n_frames=8, width=128, height=96,
                   fps=24)
    video = run(vids.create_video(db, "Fenced", source_path=str(src)))
    job_id = run(claims.enqueue_job(db, video["id"]))

    old = api["client"]
    claimed = run(old.claim(["transcode"], "tpu"))
    assert claimed["job"]["id"] == job_id
    assert claimed["job"]["attempt"] == 1      # epoch 1 in `old`

    # the lease lapses (worker partitioned); the sweep releases it
    run(db.execute("UPDATE jobs SET claim_expires_at=1 WHERE id=:id",
                   {"id": job_id}))
    run(claims.sweep_expired_claims(db))

    # the SAME worker name re-claims: a fresh incarnation, epoch 2
    successor = WorkerAPIClient(api["base"], api["key"], timeout=30.0,
                                retries=0)
    reclaimed = run(successor.claim(["transcode"], "tpu"))
    assert reclaimed["job"]["id"] == job_id
    assert reclaimed["job"]["attempt"] == 2
    row = run(db.fetch_one("SELECT * FROM jobs WHERE id=:id",
                           {"id": job_id}))
    assert row["claimed_by"] == "rw1"          # same name, new epoch

    # every stale-epoch write from the zombie bounces 409 even though
    # the ownership predicate (claimed_by == "rw1") would admit it
    with pytest.raises(ClaimLost, match="stale claim epoch"):
        run(old.progress(job_id, progress=10.0))
    evil = tmp_path / "evil.bin"
    evil.write_bytes(b"stale incarnation payload")
    with pytest.raises(ClaimLost, match="stale claim epoch"):
        run(old.upload_file(video["id"], "360p/evil.bin", evil))
    with pytest.raises(ClaimLost, match="stale claim epoch"):
        run(old.post_spans(job_id, [{
            "name": "worker.attempt", "span_id": "zombie1",
            "started_at": 1.0, "duration_s": 1.0}]))
    with pytest.raises(ClaimLost, match="stale claim epoch"):
        run(old.complete(job_id, {"qualities": []}))
    with pytest.raises(ClaimLost, match="stale claim epoch"):
        run(old.fail(job_id, "zombie says broken"))
    job_now = run(db.fetch_one("SELECT * FROM jobs WHERE id=:id",
                               {"id": job_id}))
    assert job_now["completed_at"] is None and job_now["failed_at"] is None
    assert job_now["claimed_by"] == "rw1"      # claim untouched

    # the successor incarnation runs the attempt to completion over the
    # wire (its writes carry epoch 2 and all land)
    worker = RemoteWorker(successor, name="rw1",
                          work_dir=tmp_path / "work",
                          progress_min_interval_s=0.0)

    run(worker._run_transcode(reclaimed["job"], reclaimed["video"]))
    done = run(db.fetch_one("SELECT * FROM jobs WHERE id=:id",
                            {"id": job_id}))
    assert done["completed_at"] is not None

    # the published tree verifies clean against its manifest and the
    # zombie's payload never landed in it
    root = api["video_dir"] / video["slug"]
    manifest = integrity.load_manifest(root)
    assert manifest is not None
    assert integrity.verify_tree(root, manifest) == []
    assert not (root / "360p" / "evil.bin").exists()
    assert "360p/evil.bin" not in manifest
    # completion dropped the successor's fencing state (no leak); the
    # zombie deliberately KEEPS its stale entry while its attempt is
    # considered live — it must keep bouncing, not go epochless
    assert successor._epochs == {}
    run(successor.aclose())


def test_claim_fence_failpoint_forces_stale_write(run, db, tmp_path, api):
    from vlog_tpu.worker.remote import ClaimLost

    src = make_y4m(tmp_path / "c.y4m", n_frames=6, width=64, height=48)
    video = run(vids.create_video(db, "Forced", source_path=str(src)))
    run(claims.enqueue_job(db, video["id"]))
    claimed = run(api["client"].claim(["transcode"], "tpu"))
    job_id = claimed["job"]["id"]
    run(api["client"].progress(job_id, progress=5.0))    # sanity: lands
    failpoints.arm("claim.fence", count=1)
    with pytest.raises(ClaimLost, match="stale claim epoch"):
        run(api["client"].progress(job_id, progress=9.0))
    # fencing state survives a 409: a zombie must keep bouncing, never
    # degrade to epochless writes — the spent budget means the next
    # write carries the true epoch again and lands
    assert api["client"]._epochs[job_id] == 1
    run(api["client"].progress(job_id, progress=12.0))
    row = run(db.fetch_one("SELECT * FROM jobs WHERE id=:id",
                           {"id": job_id}))
    assert row["progress"] == 12.0


def test_epochless_clients_still_pass_ownership_gates(run, db, tmp_path,
                                                      api):
    """Pre-fencing compatibility: no X-Claim-Epoch header means
    ownership checks only (the old behavior), not a 400/409."""
    import httpx

    src = make_y4m(tmp_path / "o.y4m", n_frames=6, width=64, height=48)
    video = run(vids.create_video(db, "Legacy", source_path=str(src)))
    run(claims.enqueue_job(db, video["id"]))
    claimed = run(api["client"].claim(["transcode"], "tpu"))
    job_id = claimed["job"]["id"]

    async def go():
        async with httpx.AsyncClient(
                base_url=api["base"],
                headers={"Authorization": f"Bearer {api['key']}"}) as c:
            r = await c.post(f"/api/worker/jobs/{job_id}/progress",
                             json={"progress": 33.0})
            assert r.status_code == 200
            # garbage epoch is a client bug: 400, not silently ignored
            r = await c.post(f"/api/worker/jobs/{job_id}/progress",
                             json={"progress": 34.0},
                             headers={"X-Claim-Epoch": "banana"})
            assert r.status_code == 400

    run(go())


# --------------------------------------------------------------------------
# Coordination-plane brownout
# --------------------------------------------------------------------------

class TestCoordinationBreaker:
    def test_opens_after_threshold_and_closes_on_success(self):
        clock = [0.0]
        b = CoordinationBreaker(threshold=3, cooldown_s=10.0,
                                base_backoff_s=1.0,
                                clock=lambda: clock[0])
        d1 = b.record_error(ConnectionError("refused"))
        d2 = b.record_error(ConnectionError("refused"))
        assert not b.is_open
        d3 = b.record_error(ConnectionError("refused"))
        assert b.is_open and b.opens == 1
        assert b.snapshot()["last_error"].startswith("ConnectionError")
        # jittered exponential growth, capped at the cooldown
        assert 0.5 <= d1 <= 1.5
        assert 1.0 <= d2 <= 3.0
        assert 2.0 <= d3 <= 6.0
        for _ in range(10):
            assert b.record_error(ConnectionError("x")) <= 15.0
        b.record_success()
        assert not b.is_open and b.consecutive_errors == 0

    def test_readiness_degrades_while_open(self, run):
        from vlog_tpu.worker.health import breaker_check

        b = CoordinationBreaker(threshold=1, cooldown_s=5.0)
        check = breaker_check(b)
        ok, detail = run(check())
        assert ok
        b.record_error(ConnectionError("server closed the connection"))
        ok, detail = run(check())
        assert not ok and "brownout" in detail
        b.record_success()
        ok, _ = run(check())
        assert ok


def test_transient_db_error_classification():
    import sqlite3

    from vlog_tpu.db.retry import is_transient_db_error

    assert is_transient_db_error(ConnectionError("anything"))
    assert is_transient_db_error(RuntimeError("database is locked"))
    assert is_transient_db_error(OSError("broken pipe"))
    assert is_transient_db_error(
        sqlite3.OperationalError("connection is closed"))
    pg = RuntimeError("server starting")
    pg.sqlstate = "57P03"
    assert is_transient_db_error(pg)
    assert not is_transient_db_error(ValueError("bad input"))
    assert not is_transient_db_error(RuntimeError("NOT NULL constraint"))
    # message fragments only classify on I/O / driver families: a code
    # bug whose TEXT mentions the network must not be routed into the
    # brownout path (where its traceback-level handling differs)
    assert not is_transient_db_error(
        RuntimeError("connection refused"))
    assert not is_transient_db_error(
        ValueError("backend unavailable for kind x"))


def test_daemon_brownout_on_db_claim_failures(run, db, tmp_path):
    """db.claim armed: the claim loop survives, paces onto backoff,
    opens the brownout breaker, and recovers to process the queue once
    the plane answers again."""
    src = make_y4m(tmp_path / "b.y4m", n_frames=6, width=64, height=48)
    video = run(vids.create_video(db, "Brownout", source_path=str(src)))
    job_id = run(claims.enqueue_job(db, video["id"], JobKind.SPRITE))
    run(db.execute("UPDATE videos SET duration_s=0.25 WHERE id=:i",
                   {"i": video["id"]}))

    daemon = make_daemon(
        db, tmp_path, poll_interval_s=0.05,
        db_breaker=CoordinationBreaker(threshold=2, cooldown_s=0.05,
                                       base_backoff_s=0.01))
    failpoints.arm("db.claim", count=3)

    async def go():
        task = asyncio.create_task(daemon.run())
        # the breaker opens after 2 consecutive injected faults
        for _ in range(400):
            if daemon.db_breaker.is_open:
                break
            await asyncio.sleep(0.01)
        assert daemon.db_breaker.is_open, "brownout breaker never opened"
        # once the budget is spent the plane "recovers": the loop closes
        # the breaker and drains the queue
        for _ in range(1000):
            row = await db.fetch_one("SELECT * FROM jobs WHERE id=:id",
                                     {"id": job_id})
            if row["completed_at"] is not None:
                break
            await asyncio.sleep(0.02)
        daemon.request_stop()
        await asyncio.wait_for(task, timeout=30.0)
        assert row["completed_at"] is not None
        assert not daemon.db_breaker.is_open
        assert daemon.db_breaker.opens == 1

    run(go())
    from vlog_tpu.obs.metrics import HAVE_PROMETHEUS, runtime

    if HAVE_PROMETHEUS:
        text = runtime().render_text()
        assert 'vlog_claim_errors_total{source="daemon"}' in text


def test_delivery_serves_stale_state_through_db_flap(run, db, tmp_path,
                                                     monkeypatch):
    """Publish-state brownout: a transient DB error after the TTL lapses
    serves the cached answer instead of failing playback."""
    from vlog_tpu.delivery.plane import DeliveryPlane
    from vlog_tpu.jobs import videos as vids_mod

    video = run(vids.create_video(db, "Stale", source_path=None))
    run(db.execute("UPDATE videos SET status='ready' WHERE id=:i",
                   {"i": video["id"]}))
    plane = DeliveryPlane(db, tmp_path / "videos", state_ttl_s=0.0)

    async def go():
        st = await plane.serving_state(video["slug"])
        assert st.status == "ready"

        async def flaky(*a, **kw):
            raise ConnectionError("server closed the connection")

        monkeypatch.setattr(vids_mod, "get_video_serving_state", flaky)
        # TTL 0: the next request must refresh — and hits the flap
        st2 = await plane.serving_state(video["slug"])
        assert st2.status == "ready"
        assert plane.counters["state_stale"] == 1
        # an unknown slug has no stale truth to serve: the error surfaces
        with pytest.raises(ConnectionError):
            await plane.serving_state("never-seen")
        # a non-transient error surfaces even with a cached entry
        async def broken(*a, **kw):
            raise ValueError("bad query")

        monkeypatch.setattr(vids_mod, "get_video_serving_state", broken)
        with pytest.raises(ValueError):
            await plane.serving_state(video["slug"])

    run(go())


# --------------------------------------------------------------------------
# Registry / docs agreement (the PR 2-6 lint pattern, fault-domain
# edition): classification sites, knobs, metric families, the header
# --------------------------------------------------------------------------

class TestSelfHealingAgreement:
    KNOBS = ("VLOG_QUARANTINE_THRESHOLD", "VLOG_DEVICE_PROBE_INTERVAL_S",
             "VLOG_DB_BREAKER_THRESHOLD", "VLOG_DB_BREAKER_COOLDOWN")
    METRICS = ("vlog_slot_quarantined_total", "vlog_device_quarantined",
               "vlog_device_probe_total", "vlog_claim_errors_total",
               "vlog_claim_breaker_open", "vlog_delivery_stale_state_total")

    def test_every_failure_class_has_a_classification_site(self):
        """Each FailureClass value must be ASSIGNED somewhere in the
        package (outside enums.py) — an enum member nothing classifies
        into is dead vocabulary that rots the dead-letter view."""
        import re
        from pathlib import Path

        pkg = Path(__file__).parent.parent / "vlog_tpu"
        used = set()
        for p in pkg.rglob("*.py"):
            if p.name == "enums.py":
                continue
            src = p.read_text()
            used.update(re.findall(r"FailureClass\.([A-Z_]+)", src))
            # string-form classifications (sweep/release paths)
            for m in FailureClass:
                if f'"{m.value}"' in src or f"'{m.value}'" in src:
                    used.add(m.name)
        missing = {m.name for m in FailureClass} - used
        assert not missing, \
            f"FailureClass members with no classification site: {missing}"

    def test_knobs_parsed_and_documented(self):
        from vlog_tpu.analysis import registry as reg

        reg.assert_knobs(self.KNOBS)
        assert isinstance(config.QUARANTINE_THRESHOLD, int)
        assert isinstance(config.DEVICE_PROBE_INTERVAL_S, float)

    def test_metrics_registered_and_documented(self):
        from vlog_tpu.analysis import registry as reg

        reg.assert_metric_families(self.METRICS)

    def test_fencing_header_documented_and_new_sites_registered(self):
        from vlog_tpu.analysis import registry as reg

        reg.assert_documented(("X-Claim-Epoch",))
        reg.assert_failpoint_sites(("device.fault", "claim.fence",
                                    "db.claim"))
        # arm_from_spec accepts them (the VLOG_FAILPOINTS contract)
        armed = failpoints.arm_from_spec(
            "device.fault=1,claim.fence=1,db.claim=1")
        assert set(armed) == {"device.fault", "claim.fence", "db.claim"}
        failpoints.reset()

    def test_new_sites_observable(self):
        """add_observer coverage for the new sites: every fire reaches
        registered observers (and therefore the fires counter)."""
        seen = []
        observer = seen.append
        failpoints.add_observer(observer)
        try:
            for site in ("device.fault", "claim.fence", "db.claim"):
                failpoints.arm(site, count=1)
                with pytest.raises(failpoints.FailpointError):
                    failpoints.hit(site)
        finally:
            failpoints.reset()
            if observer in failpoints._observers:
                failpoints._observers.remove(observer)
        assert seen == ["device.fault", "claim.fence", "db.claim"]
