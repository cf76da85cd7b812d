"""Perf observatory (ISSUE-19): SLO burn-rate plane, on-demand device
profiling, and the bench-trend regression gate.

Covers the acceptance surface: the benchtrend parser round-trips every
committed BENCH_*.json / MULTICHIP*.json file at HEAD (schema drift
breaks here, not silently in the gate), ``--check`` exits 0 at HEAD
and 1 on a synthetically regressed record, gating respects
``gate: tpu_only`` and fallback labels; SLO burn-rate math units over
histogram/counter windows; ``GET /api/slo`` serves live burn rates for
every objective with exemplars whose trace_ids resolve through
``GET /api/jobs/{id}/trace``; the exemplar ring is bounded; profiler
sessions start/stop with artifact containment; the /metrics DB block
is TTL-cached; and the registry lints for every new family and knob.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import pytest
from aiohttp.test_utils import TestServer

from vlog_tpu import config
from vlog_tpu.jobs import claims, videos as vids
from vlog_tpu.obs import benchtrend as bt, slo as slomod, store as obs_store
from vlog_tpu.obs.metrics import runtime
from tests.fixtures.media import make_y4m

REPO = Path(__file__).resolve().parents[1]


# --------------------------------------------------------------------------
# benchtrend: parser round-trip + gate semantics
# --------------------------------------------------------------------------

class TestBenchtrend:
    def test_round_trips_every_committed_file(self):
        """Every committed trajectory file parses; the known-labeled
        ones yield points. Schema drift in a future bench round fails
        HERE, in tier-1, instead of silently emptying the gate."""
        files = bt.bench_files(REPO)
        # the five CPU-VM trajectories; the old driver wrapper records
        # (BENCH_r0N / MULTICHIP_r0N) are gone and must not come back
        assert len(files) >= 5
        assert not [f.name for f in files if "_r0" in f.name]
        by_file: dict[str, int] = {}
        for f in files:
            pts = bt.parse_file(f, f.name)    # must not raise
            by_file[f.name] = len(pts)
        for name in ("BENCH_asr.json", "BENCH_compile.json",
                     "BENCH_coord.json", "BENCH_delivery.json",
                     "MULTICHIP.json"):
            assert by_file.get(name, 0) >= 1, (name, by_file)
        assert sum(by_file.values()) >= 40

    def test_head_is_green(self):
        rep = bt.trend_report(REPO)
        assert rep["ok"], rep["regressions"]
        assert rep["series"] >= 20
        assert rep["gated_points"] >= 40

    def _seed(self, tmp_path: Path) -> Path:
        root = tmp_path / "traj"
        root.mkdir()
        for f in bt.bench_files(REPO):
            shutil.copy(f, root / f.name)
        return root

    def test_check_exit_codes(self, tmp_path):
        root = self._seed(tmp_path)
        assert bt.main(["--check", "--root", str(root)]) == 0
        # synthetically regress the latest point of a real series
        path = root / "BENCH_coord.json"
        data = json.loads(path.read_text())
        tmpl = dict(next(r for r in data if r.get("step") == "poll_only"
                         and r.get("metric") == "coord_claims_per_s"))
        tmpl["rps"] = 1.0
        tmpl["timestamp"] = "2099-01-01T00:00:00Z"
        data.append(tmpl)
        path.write_text(json.dumps(data))
        assert bt.main(["--check", "--root", str(root)]) == 1
        regs = bt.trend_report(root)["regressions"]
        assert any(r["metric"] == "coord_claims_per_s" for r in regs)

    def test_tpu_only_and_fallback_records_never_gate(self, tmp_path):
        root = tmp_path / "t2"
        root.mkdir()
        base = [{"metric": "fix_device_realtime_x", "value": 100.0,
                 "gate": "tpu_only",
                 "timestamp": "2026-01-01T00:00:00Z"}]
        # a cpu-platform point and a fallback point, both cratered
        bad_cpu = {"metric": "fix_device_realtime_x", "value": 1.0,
                   "gate": "tpu_only", "platform": "cpu",
                   "timestamp": "2026-02-01T00:00:00Z"}
        bad_fb = {"metric": "fix_device_realtime_x", "value": 1.0,
                  "gate": "tpu_only",
                  "fallback_reason": "tpu_body_timed_out",
                  "timestamp": "2026-03-01T00:00:00Z"}
        (root / "BENCH_fix.json").write_text(
            json.dumps(base + [bad_cpu, bad_fb]))
        rep = bt.trend_report(root)
        assert rep["ok"], rep["regressions"]
        # the same crater WITH native platform labels gates
        bad_tpu = {"metric": "fix_device_realtime_x", "value": 1.0,
                   "gate": "tpu_only",
                   "timestamp": "2026-04-01T00:00:00Z"}
        (root / "BENCH_fix.json").write_text(
            json.dumps(base + [bad_tpu]))
        rep = bt.trend_report(root)
        assert not rep["ok"]

    def test_lower_is_better_and_abs_floor(self, tmp_path):
        root = tmp_path / "t3"
        root.mkdir()
        # sub-floor latency jitter (1.5ms -> 3.1ms) never gates...
        tiny = [{"metric": "fix_wait_p99_s", "value": 0.0015,
                 "timestamp": "2026-01-01T00:00:00Z"},
                {"metric": "fix_wait_p99_s", "value": 0.0031,
                 "timestamp": "2026-02-01T00:00:00Z"}]
        (root / "BENCH_fix.json").write_text(json.dumps(tiny))
        assert bt.trend_report(root)["ok"]
        # ...but a real above-floor latency cliff does
        big = [{"metric": "fix_wait_p99_s", "value": 0.2,
                "timestamp": "2026-01-01T00:00:00Z"},
               {"metric": "fix_wait_p99_s", "value": 2.0,
                "timestamp": "2026-02-01T00:00:00Z"}]
        (root / "BENCH_fix.json").write_text(json.dumps(big))
        rep = bt.trend_report(root)
        assert not rep["ok"]
        assert rep["regressions"][0]["lower_is_better"] is True

    def test_wrapper_and_legacy_shapes(self, tmp_path):
        root = tmp_path / "t4"
        root.mkdir()
        # runner wrapper: record only in the captured tail
        (root / "BENCH_r99.json").write_text(json.dumps({
            "n": 99, "rc": 0,
            "tail": "noise\n" + json.dumps(
                {"metric": "fix_tail_x", "value": 7.0}) + "\n"}))
        # legacy unlabeled delivery shape expands *_rps facets
        (root / "BENCH_legacy.json").write_text(json.dumps([
            {"metric": "segment_delivery", "hot_cache_rps": 1000.0,
             "cold_origin_rps": 100.0, "speedup_x": 10.0}]))
        pts = bt.load_trajectory(root)
        metrics = {p.metric for p in pts}
        assert "fix_tail_x" in metrics
        assert "segment_delivery_hot_cache_rps" in metrics
        assert "segment_delivery_cold_origin_rps" in metrics


# --------------------------------------------------------------------------
# SLO plane: burn-rate math units
# --------------------------------------------------------------------------

class TestSloMath:
    def test_histogram_cum_threshold_snaps_to_bucket(self):
        from prometheus_client import CollectorRegistry, Histogram

        h = Histogram("fixm_lat_seconds", "d", ["l"],
                      buckets=(0.1, 1.0, 10.0),
                      registry=CollectorRegistry())
        for v in (0.05, 0.5, 5.0, 50.0):
            h.labels("a").observe(v)
        # threshold 1.0 -> le=1.0 bucket: 2 good of 4
        assert slomod._histogram_cum(h, 1.0) == (2.0, 4.0)
        # threshold between buckets snaps UP to the next bound
        assert slomod._histogram_cum(h, 0.5) == (2.0, 4.0)
        # threshold past the largest finite bucket: only +Inf -> all good
        assert slomod._histogram_cum(h, 100.0) == (4.0, 4.0)

    def test_counter_cum_bad_values(self):
        from prometheus_client import CollectorRegistry, Counter

        c = Counter("fixm_req", "d", ["outcome"],
                    registry=CollectorRegistry())
        c.labels("hit").inc(90)
        c.labels("miss").inc(8)
        c.labels("shed").inc(2)
        good, total = slomod._counter_cum(c, ("shed",))
        assert (good, total) == (98.0, 100.0)

    def test_window_delta_and_burn(self, monkeypatch):
        plane = slomod.SloPlane()
        name = plane.objectives[0].name
        t0 = time.time()
        with plane._lock:
            plane._ring.append((t0 - 100.0, {name: (100.0, 100.0)}))
            plane._ring.append((t0, {name: (104.0, 110.0)}))
        dg, dt, w = plane._window_delta(name, t0, 300.0)
        assert (dg, dt) == (4.0, 10.0)
        assert w == pytest.approx(100.0, abs=1.0)
        # 60% error over a 95% objective = burn 12x
        obj = plane.objectives[0]
        err = 1.0 - dg / dt
        assert err / obj.budget == pytest.approx(
            0.6 / (1.0 - obj.target), rel=1e-6)

    def test_registry_restart_clamps_negative_delta(self):
        plane = slomod.SloPlane()
        name = plane.objectives[0].name
        t0 = time.time()
        with plane._lock:
            plane._ring.append((t0 - 100.0, {name: (500.0, 500.0)}))
            plane._ring.append((t0, {name: (3.0, 5.0)}))
        dg, dt, _ = plane._window_delta(name, t0, 300.0)
        assert (dg, dt) == (3.0, 5.0)


# --------------------------------------------------------------------------
# SLO plane: live report over HTTP + exemplar -> trace resolvability
# --------------------------------------------------------------------------

def _insert_span(run, db, job_id, trace_id, span_id, name, duration_s,
                 parent_id="root", attrs=None):
    run(db.execute(
        "INSERT INTO job_spans (job_id, trace_id, span_id, parent_id,"
        " name, origin, started_at, duration_s, status, attributes,"
        " created_at) VALUES (:j, :tid, :sid, :pid, :name, 'server',"
        " :start, :dur, 'ok', :attrs, :t)",
        {"j": job_id, "tid": trace_id, "sid": span_id, "pid": parent_id,
         "name": name, "start": time.time() - duration_s,
         "dur": duration_s, "attrs": json.dumps(attrs or {}),
         "t": time.time()}))


@pytest.fixture
def slo_plane():
    slomod.reset_plane()
    yield slomod.plane()
    slomod.reset_plane()


def test_api_slo_live_report_with_resolvable_exemplars(
        run, db, tmp_path, slo_plane):
    """GET /api/slo (worker app, auth-exempt) reports burn rates for
    every objective; a slow queue.wait outlier surfaces as an exemplar
    whose trace_id/job_id resolve through the admin trace endpoint."""
    from vlog_tpu.api.admin_api import build_admin_app
    from vlog_tpu.api.worker_api import build_worker_app

    src = make_y4m(tmp_path / "s.y4m", n_frames=4, width=64, height=48)
    video = run(vids.create_video(db, "SLO", source_path=str(src)))
    job_id = run(claims.enqueue_job(db, video["id"]))
    trace_id, root_id, _ = run(obs_store.ensure_root(db, job_id))

    wait_obj = next(o for o in slo_plane.objectives
                    if o.span_name == "queue.wait")
    _insert_span(run, db, job_id, trace_id, "slow-wait", "queue.wait",
                 wait_obj.threshold_s * 3, parent_id=root_id,
                 attrs={"tenant": "default", "attempt": 1})
    # a closed root over the enqueue->ready threshold as well
    run(db.execute(
        "UPDATE job_spans SET duration_s=:d WHERE job_id=:j"
        " AND parent_id IS NULL",
        {"d": 3 * next(o for o in slo_plane.objectives
                       if o.span_name == "__root__").threshold_s,
         "j": job_id}))
    # drive the registry-backed objectives so every kind reports
    m = runtime()
    m.tenant_claim_wait.labels("default").observe(0.1)
    m.delivery_fill_seconds.labels("ram").observe(0.01)
    m.delivery_requests.labels("hit").inc(10)
    m.asr_windows_per_second.set(12.0)
    m.asr_batch_occupancy.set(0.9)

    srv = TestServer(build_worker_app(db, video_dir=tmp_path / "vids"))
    admin = TestServer(build_admin_app(db, upload_dir=tmp_path / "up",
                                       video_dir=tmp_path / "vids"))
    import httpx

    async def go():
        await srv.start_server()
        await admin.start_server()
        async with httpx.AsyncClient(base_url=str(srv.make_url(""))) as c:
            # auth-exempt like /metrics and scale-hint
            rep = (await c.get("/api/slo")).json()
        assert len(rep["objectives"]) >= 5
        for o in rep["objectives"]:
            for w in ("fast", "slow"):
                assert "burn_rate" in o["windows"][w]
        by_name = {o["name"]: o for o in rep["objectives"]}
        assert by_name["jobs.queue_wait"]["windows"]["fast"]["events"] >= 1
        assert by_name["jobs.queue_wait"]["windows"]["fast"][
            "error_ratio"] > 0
        exes = [e for e in rep["exemplars"] if e["job_id"] == job_id]
        assert exes, rep["exemplars"]
        assert all(e["trace_id"] == trace_id for e in exes)
        wait_ex = next(e for e in exes
                       if e["objective"] == "jobs.queue_wait")
        assert wait_ex["attrs"].get("tenant") == "default"
        async with httpx.AsyncClient(
                base_url=str(admin.make_url(""))) as c:
            tr = (await c.get(f"/api/jobs/{job_id}/trace")).json()
        assert tr["trace_id"] == trace_id
        await srv.close()
        await admin.close()

    run(go())
    # the same alerting state feeds the scale-hint floor
    from vlog_tpu.jobs import qos

    snap = run(qos.fleet_snapshot(db))
    assert "slo_alerts" in snap
    for name in snap["slo_alerts"]:
        assert name.startswith("jobs.")


def test_exemplar_ring_is_bounded(run, db, tmp_path, monkeypatch):
    monkeypatch.setattr(config, "SLO_EXEMPLARS", 3)
    slomod.reset_plane()
    try:
        plane = slomod.plane()
        src = make_y4m(tmp_path / "b.y4m", n_frames=4, width=64,
                       height=48)
        wait_obj = next(o for o in plane.objectives
                        if o.span_name == "queue.wait")
        for i in range(8):
            video = run(vids.create_video(db, f"Ring{i}",
                                          source_path=str(src)))
            job_id = run(claims.enqueue_job(db, video["id"]))
            trace_id, root_id, _ = run(obs_store.ensure_root(db, job_id))
            _insert_span(run, db, job_id, trace_id, f"w{i}",
                         "queue.wait", wait_obj.threshold_s * (2 + i),
                         parent_id=root_id)
        rep = run(plane.evaluate(db))
        assert 0 < len(rep["exemplars"]) <= 3
    finally:
        slomod.reset_plane()


def test_metrics_db_block_is_ttl_cached(run, db, monkeypatch):
    from vlog_tpu.obs.metrics import Metrics

    monkeypatch.setattr(config, "METRICS_DB_TTL_S", 60.0)
    m = Metrics()
    calls = {"n": 0}
    orig = db.fetch_all

    async def counting(*a, **k):
        calls["n"] += 1
        return await orig(*a, **k)

    monkeypatch.setattr(db, "fetch_all", counting)
    run(m.render(db))
    first = calls["n"]
    assert first > 0
    run(m.render(db))
    assert calls["n"] == first      # within TTL: no extra SQL
    monkeypatch.setattr(config, "METRICS_DB_TTL_S", 0.0)
    m2 = Metrics()
    run(m2.render(db))
    run(m2.render(db))
    assert calls["n"] > 2 * first   # TTL 0: every scrape queries


# --------------------------------------------------------------------------
# Profiler sessions
# --------------------------------------------------------------------------

class TestProfiler:
    def test_refuses_when_jax_uninitialized(self, monkeypatch, tmp_path):
        from vlog_tpu.obs.profiler import DeviceProfiler

        monkeypatch.setattr(config, "PROFILE_DIR", str(tmp_path))
        monkeypatch.delitem(sys.modules, "jax", raising=False)
        out = DeviceProfiler().start(duration_s=5)
        assert "error" in out and "jax" in out["error"]

    def test_start_stop_containment_and_exclusivity(
            self, monkeypatch, tmp_path):
        from vlog_tpu.obs.profiler import DeviceProfiler

        jax = pytest.importorskip("jax")
        assert jax is sys.modules["jax"]
        root = tmp_path / "prof"
        monkeypatch.setattr(config, "PROFILE_DIR", str(root))
        p = DeviceProfiler()
        info = p.start(duration_s=30.0, label="../../../etc/passwd x")
        try:
            assert info.get("profiling") is True, info
            target = Path(info["dir"]).resolve()
            # hostile label stays inside the artifact root
            assert target.is_relative_to(root.resolve())
            assert "/" not in target.name and " " not in target.name
            # exclusive: second start is rejected, not queued
            again = p.start(duration_s=5)
            assert "already active" in again["error"]
            st = p.status()
            assert st["profiling"] is True
            assert st["remaining_s"] <= 30.0
        finally:
            out = p.stop()
        assert out["profiling"] is False
        assert out.get("error") is None
        # idempotent
        assert "no active session" in p.stop()["error"]
        assert target.name in p.list_sessions()
        fam = runtime().profile_sessions
        started = fam.labels("started")._value.get()
        assert started >= 1

    def test_timer_auto_stops_session(self, monkeypatch, tmp_path):
        from vlog_tpu.obs.profiler import DeviceProfiler

        pytest.importorskip("jax")
        monkeypatch.setattr(config, "PROFILE_DIR", str(tmp_path / "p2"))
        p = DeviceProfiler()
        info = p.start(duration_s=1.0)
        assert info.get("profiling") is True, info
        deadline = time.monotonic() + 10.0
        while p.status()["profiling"] and time.monotonic() < deadline:
            time.sleep(0.1)
        assert p.status()["profiling"] is False

    @pytest.mark.parametrize("source", ["explicit", "timer"])
    def test_stop_leaves_a_summary_beside_the_artifact(
            self, monkeypatch, tmp_path, source):
        """Either way of stopping writes ``summary.json``; the explicit
        stop (it arrives on the heartbeat task) only names the file and
        leaves the writing to a thread of its own."""
        from vlog_tpu.obs.profiler import DeviceProfiler

        pytest.importorskip("jax")
        monkeypatch.setattr(config, "PROFILE_DIR", str(tmp_path / source))
        p = DeviceProfiler()
        info = p.start(duration_s=1.0 if source == "timer" else 30.0)
        assert info.get("profiling") is True, info
        summary = Path(info["dir"]) / "summary.json"
        if source == "explicit":
            out = p.stop()
            assert out["summary"] == str(summary)
            p.wait_summary(30.0)
        else:
            deadline = time.monotonic() + 20.0
            while not summary.exists() and time.monotonic() < deadline:
                time.sleep(0.1)
        got = json.loads(summary.read_text())
        assert set(got) == {"window_s", "busy_s", "devices_traced",
                            "by_scope", "idle_by_span", "programs"}
        assert p.status()["profiling"] is False

    def test_mgmt_profile_verb_dispatch(self, monkeypatch, tmp_path):
        from vlog_tpu.worker import mgmt

        monkeypatch.setattr(config, "PROFILE_DIR", str(tmp_path / "p3"))
        assert "error" in mgmt.profile({"action": "bogus"})
        st = mgmt.profile({"action": "status"})
        assert st["profiling"] is False
        assert st["root"].endswith("p3")


# --------------------------------------------------------------------------
# The reduction of a capture by the program's own names
# --------------------------------------------------------------------------

FIXTURES = Path(__file__).resolve().parent / "fixtures"


@pytest.mark.parametrize("name,want", [
    ("jit(f)/jit(main)/asr.decoder_step/asr.decoder_step.mlp/dot_general",
     "asr.decoder_step.mlp"),
    ("jit(local)/while/body/vmap(ladder.mc)/gather", "ladder.mc"),
    ("jit(f)/transpose(jvp(asr.encoder))/mul", "asr.encoder"),
    ("asr.mel", "asr.mel"),
    ("jit(f)/while/body/add", None),
    ("fusion.3", None),
    ("jit(basr.mel)/x", None),
])
def test_scope_of_takes_the_innermost_program_scope(name, want):
    from vlog_tpu.obs.profiler import scope_of

    assert scope_of(name) == want


def test_hlo_scopes_name_a_fusion_by_what_it_holds():
    from vlog_tpu.obs.profiler import hlo_scopes

    text = '''HloModule jit_f, is_scheduled=true

%fused_computation (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %t = f32[4]{0} tanh(%p0), metadata={op_name="jit(f)/asr.encoder/tanh"}
  ROOT %g = f32[4]{0} negate(%t), metadata={op_name="jit(f)/asr.beam_reorder/neg"}
}

fused_computation.1 {
  p1 = f32[4]{0} parameter(0)
  a = f32[4]{0} abs(p1), metadata={op_name="jit(f)/asr.token_rules/abs"}
  ROOT c = f32[4]{0} copy(a)
}

ENTRY %main.1 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %fusion.2 = f32[4]{0} fusion(%x), kind=kLoop, calls=fused_computation.1
  %named = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/asr.prompt/x"}
  ROOT %plain = f32[4]{0} add(%fusion.1, %fusion.2)
}
'''
    got = hlo_scopes(text)
    assert got["fusion.1"] == "asr.beam_reorder"    # its root's
    assert got["fusion.2"] == "asr.token_rules"     # most of its members'
    assert got["named"] == "asr.prompt"             # its own first
    assert got["t"] == "asr.encoder"
    assert "plain" not in got and "x" not in got


def test_summarize_reads_the_capture_recorded_on_the_chip(tmp_path):
    """One 1 x 5 tick at tiny widths, recorded on the TPU by
    ``tests/fixtures/record_asr_trace.py``: the reduction of the kept
    capture is the one written beside it, and it reads as the program's
    layers (the framework names come from the HLO protos the capture
    holds; its events carry none)."""
    import gzip

    from vlog_tpu.obs.profiler import summarize

    pb = tmp_path / "asr_tick.xplane.pb"
    pb.write_bytes(gzip.decompress(
        (FIXTURES / "asr_tick.xplane.pb.gz").read_bytes()))
    got = summarize(pb)
    want = json.loads((FIXTURES / "asr_tick.summary.json").read_text())
    assert json.loads(json.dumps(got)) == want
    assert got["devices_traced"] == 1
    assert 0.0 < got["busy_s"] < got["window_s"]
    assert sum(got["by_scope"].values()) == pytest.approx(got["busy_s"])
    beam = got["programs"]["jit__generate_beam_jit"]
    assert beam["runs"] == 1
    assert {"asr.beam_reorder", "asr.beam_ancestry", "asr.beam_select",
            "asr.token_rules", "asr.decoder_step.self_attn", "asr.decoder_step.cross_attn",
            "asr.decoder_step.mlp", "asr.decoder_step.logits",
            "asr.decoder_step.cache_update", "asr.encoder.attn",
            "asr.cross_kv", "asr.prompt", "asr.beam_final"} <= set(
                beam["by_scope"])
    assert beam["by_scope"].get("unscoped", 0.0) < 0.1 * beam["seconds"]
    assert set(got["programs"]["jit_log_mel_spectrogram"]["by_scope"]) \
        <= {"asr.mel", "unscoped"}
    # the device was idle while the engine waited for the window and
    # while it pulled the tokens; both gaps carry the engine's own names
    assert set(got["idle_by_span"]) == {"asr.tick.coalesce",
                                        "asr.generate.device_wait"}


# --------------------------------------------------------------------------
# Registry lints: every new family and knob is documented + registered
# --------------------------------------------------------------------------

def test_registry_lints_for_observatory_surface():
    from vlog_tpu.analysis import registry as reg

    reg.assert_knobs((
        "VLOG_SLO_FAST_WINDOW_S", "VLOG_SLO_SLOW_WINDOW_S",
        "VLOG_SLO_EVAL_S", "VLOG_SLO_EXEMPLARS", "VLOG_SLO_BURN_ALERT",
        "VLOG_PROFILE_DIR", "VLOG_PROFILE_MAX_S",
        "VLOG_METRICS_DB_TTL_S", "VLOG_BENCHTREND_TOL",
    ))
    reg.assert_metric_families((
        "vlog_slo_error_ratio", "vlog_slo_burn_rate", "vlog_slo_alert",
        "vlog_slo_exemplars_total", "vlog_device_seconds_total",
        "vlog_profile_sessions_total",
    ))
