"""Trace plane + unified metrics registry (vlog_tpu/obs/).

Covers the ISSUE-4 acceptance surface: span-tree assembly under
concurrency, one trace id stitching server and worker spans across a
full HTTP claim->transcode->upload->complete cycle, stage-duration
histograms on both /metrics endpoints, a failpoint-induced failure
producing an error-tagged span, the O(states) scrape aggregate, and
the lint-style registry/docs agreement tests (metric names, failpoint
sites, observability knobs).
"""

from __future__ import annotations

import glob
import sys
import threading
import time
from pathlib import Path

import pytest
from aiohttp.test_utils import TestServer

from vlog_tpu import config
from vlog_tpu.api.admin_api import build_admin_app
from vlog_tpu.api.worker_api import build_worker_app
from vlog_tpu.jobs import claims, videos as vids
from vlog_tpu.obs import store as obs_store, trace as obs_trace
from vlog_tpu.obs.metrics import Metrics, runtime
from vlog_tpu.utils import failpoints
from vlog_tpu.worker.remote import RemoteWorker, WorkerAPIClient
from tests.fixtures.media import make_y4m



# --------------------------------------------------------------------------
# Tracer units
# --------------------------------------------------------------------------

def test_span_nesting_and_error_tagging():
    buf = obs_trace.TraceBuffer()
    ctx = obs_trace.TraceContext(obs_trace.new_id(), None, buf)
    with obs_trace.attach(ctx):
        with obs_trace.span("outer", k="v") as outer:
            with obs_trace.span("inner") as inner:
                pass
        with pytest.raises(RuntimeError):
            with obs_trace.span("boom"):
                raise RuntimeError("bad")
    spans = {s.name: s for s in buf.drain()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["outer"].trace_id == ctx.trace_id
    assert spans["outer"].duration_s is not None
    assert spans["outer"].attrs == {"k": "v"}
    assert spans["boom"].status == "error"
    assert "bad" in spans["boom"].attrs["error"]


def test_span_without_context_is_dropped_but_safe():
    with obs_trace.span("orphan") as sp:
        pass
    assert sp.duration_s is not None   # timed, just not collected


def test_span_starts_on_both_clocks_and_stores_the_old_shape():
    before = time.monotonic()
    with obs_trace.span("timed") as sp:
        time.sleep(0.01)
    assert before <= sp.started_mono <= time.monotonic()
    assert sp.ended_mono == pytest.approx(sp.started_mono + sp.duration_s)
    assert sp.duration_s >= 0.01
    assert set(sp.to_dict()) == {"trace_id", "span_id", "parent_id", "name",
                                 "started_at", "duration_s", "status",
                                 "attrs"}


class _FakeAnnotation:
    log: list = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        self.log.append(("open", self.name, self.kwargs))

    def __exit__(self, *exc):
        self.log.append(("close", self.name))


@pytest.mark.parametrize("jax_imported", [True, False])
def test_span_mirrors_into_the_profiler_only_where_jax_is(monkeypatch,
                                                          jax_imported):
    """``vlog:<name>`` annotations, properly nested, carrying the scalar
    attrs given at the open, and none at all in a process that has not
    imported jax (an API process must never pay for it)."""
    import jax

    monkeypatch.setattr(_FakeAnnotation, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    if not jax_imported:
        monkeypatch.delitem(sys.modules, "jax")
    with obs_trace.span("asr.tick", seq=3, rows=[8]) as outer:
        with obs_trace.span("asr.tick.mel"):
            pass
        with pytest.raises(ValueError):
            with obs_trace.span("asr.tick.generate"):
                raise ValueError("x")
    assert outer.duration_s is not None
    if not jax_imported:
        assert _FakeAnnotation.log == []
        return
    assert _FakeAnnotation.log == [
        ("open", "vlog:asr.tick", {"seq": 3}),
        ("open", "vlog:asr.tick.mel", {}), ("close", "vlog:asr.tick.mel"),
        ("open", "vlog:asr.tick.generate", {}),
        ("close", "vlog:asr.tick.generate"),
        ("close", "vlog:asr.tick")]


def test_spans_land_in_a_real_capture_on_the_traces_clock(tmp_path):
    """A CPU capture read back: the program's spans are there as
    ``vlog:`` events, the child inside the parent."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs_trace.span("asr.tick", seq=7):
            with obs_trace.span("asr.tick.stack"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    pb = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                       / "*.xplane.pb"))[0]
    found = {}
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("vlog:"):
                    found[ev.name] = (ev.start_ns, ev.start_ns
                                      + ev.duration_ns, dict(ev.stats))
    assert set(found) == {"vlog:asr.tick", "vlog:asr.tick.stack"}
    outer, inner = found["vlog:asr.tick"], found["vlog:asr.tick.stack"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]
    assert inner[1] - inner[0] >= 2e6                   # ns
    assert str(outer[2].get("seq")) == "7"


def test_span_tree_assembly_under_concurrency():
    """Spans created from 8 threads (explicit context hand-off, the
    compute-thread contract) all land in one buffer and assemble into
    one tree under the root."""
    buf = obs_trace.TraceBuffer()
    ctx = obs_trace.TraceContext(obs_trace.new_id(), None, buf)
    with obs_trace.attach(ctx):
        with obs_trace.span("root") as root:
            snapshot = obs_trace.capture()

            def work(i: int) -> None:
                with obs_trace.attach(snapshot):
                    with obs_trace.span(f"thread-{i}"):
                        with obs_trace.span(f"leaf-{i}"):
                            pass

            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    spans = buf.drain()
    assert len(spans) == 17                      # root + 8x(thread+leaf)
    assert {s.trace_id for s in spans} == {ctx.trace_id}
    tree = obs_store.build_tree(
        [{**s.to_dict(), "children": []} for s in spans])
    [root_node] = [n for n in tree if n["name"] == "root"]
    assert len(root_node["children"]) == 8
    for child in root_node["children"]:
        assert len(child["children"]) == 1
        assert child["children"][0]["name"] == f"leaf-{child['name'][7:]}"
    assert root.span_id == root_node["span_id"]


def test_build_tree_breaks_parent_cycles():
    """Worker-supplied parent ids are arbitrary: a mutual-parent cycle
    must surface (earliest node promoted to root), never vanish or
    recurse forever."""
    def node(sid, pid):
        return {"span_id": sid, "parent_id": pid, "name": sid,
                "children": []}

    a, b, c = node("a", "b"), node("b", "c"), node("c", "a")
    ok = node("ok", None)
    roots = obs_store.build_tree([ok, a, b, c])
    seen = []
    stack = list(roots)
    while stack:
        n = stack.pop()
        seen.append(n["span_id"])
        stack.extend(n["children"])
    assert sorted(seen) == ["a", "b", "c", "ok"], seen
    assert {r["span_id"] for r in roots} == {"ok", "a"}


def test_build_tree_orphans_surface_as_roots():
    nodes = [
        {"span_id": "a", "parent_id": None, "name": "root", "children": []},
        {"span_id": "b", "parent_id": "missing", "name": "orphan",
         "children": []},
    ]
    roots = obs_store.build_tree(nodes)
    assert {n["name"] for n in roots} == {"root", "orphan"}


def test_record_run_stages_synthesizes_leaves():
    buf = obs_trace.TraceBuffer()
    ctx = obs_trace.TraceContext(obs_trace.new_id(), None, buf)
    with obs_trace.attach(ctx):
        with obs_trace.span("worker.transcode") as tsp:
            pass
        obs_trace.record_run_stages(tsp, {
            "entropy_s": 1.5, "device_pull_s": 0.25, "rung_360p_s": 0.75,
            "pipeline_depth": 2, "host_occupancy": 1.4})
    by_name = {s.name: s for s in buf.drain()}
    assert by_name["stage.entropy"].duration_s == 1.5
    assert by_name["stage.entropy"].parent_id == tsp.span_id
    assert by_name["rung.360p"].duration_s == 0.75
    assert tsp.attrs["pipeline_depth"] == 2
    assert tsp.attrs["host_occupancy"] == 1.4


# --------------------------------------------------------------------------
# Full HTTP cycle: one trace id stitches server and worker
# --------------------------------------------------------------------------

@pytest.fixture
def api(run, db, tmp_path):
    video_dir = tmp_path / "srv-videos"
    app = build_worker_app(db, video_dir=video_dir)
    server = TestServer(app)
    run(server.start_server())
    base = str(server.make_url(""))
    key = run(WorkerAPIClient.register(base, "obs-w1", accelerator="tpu"))
    client = WorkerAPIClient(base, key, timeout=30.0, retries=1)
    yield {"base": base, "client": client, "video_dir": video_dir, "db": db}
    run(client.aclose())
    run(server.close())


def test_trace_stitches_full_remote_cycle(run, db, tmp_path, api):
    """claim -> transcode -> upload -> complete over HTTP: one trace id
    across server- and worker-origin spans; stage/rung leaves carry
    durations; both the trace endpoint and /metrics expose it."""
    src = make_y4m(tmp_path / "t.y4m", n_frames=8, width=64, height=48)
    video = run(vids.create_video(db, "Traced", source_path=str(src)))
    run(claims.enqueue_job(db, video["id"]))

    worker = RemoteWorker(api["client"], name="obs-w1",
                          work_dir=tmp_path / "work",
                          progress_min_interval_s=0.0)
    assert run(worker.poll_once()) is True
    job = run(db.fetch_one(
        "SELECT * FROM jobs WHERE video_id=:v AND kind='transcode'",
        {"v": video["id"]}))
    assert job["completed_at"] is not None, job["error"]

    rows = run(db.fetch_all("SELECT * FROM job_spans WHERE job_id=:j",
                            {"j": job["id"]}))
    assert {r["trace_id"] for r in rows} == {rows[0]["trace_id"]}
    assert {"server", "worker"} <= {r["origin"] for r in rows}
    names = {r["name"] for r in rows}
    assert {"job", "queue.wait", "server.claim", "worker.download",
            "worker.transcode", "worker.upload", "server.complete",
            "job.complete"} <= names
    # the root closed with the job
    root = next(r for r in rows if r["parent_id"] is None)
    assert root["duration_s"] is not None and root["duration_s"] > 0

    # trace endpoint returns the ordered tree with stage/rung leaves
    admin = TestServer(build_admin_app(db, upload_dir=tmp_path / "up",
                                       video_dir=api["video_dir"]))
    run(admin.start_server())
    import httpx

    async def check():
        async with httpx.AsyncClient(
                base_url=str(admin.make_url(""))) as c:
            r = await c.get(f"/api/jobs/{job['id']}/trace")
            assert r.status_code == 200
            body = r.json()
            assert body["trace_id"] == rows[0]["trace_id"]

            def walk(nodes, depth=0):
                for n in nodes:
                    yield n, depth
                    yield from walk(n["children"], depth + 1)

            flat = dict((n["name"], n) for n, _ in walk(body["spans"]))
            stage_leaves = [n for n in flat.values()
                            if n["name"].startswith("stage.")]
            rung_leaves = [n for n in flat.values()
                           if n["name"].startswith("rung.")]
            assert stage_leaves and rung_leaves
            assert all(n["duration_s"] is not None for n in stage_leaves)
            assert all(n["duration_s"] is not None for n in rung_leaves)
            assert not flat["worker.transcode"]["children"] == []
            r404 = await c.get("/api/jobs/999999/trace")
            assert r404.status_code == 404
        # server /metrics: stage histograms (observed from the posted
        # spans) + runtime counters + O(states) job gauges
        async with httpx.AsyncClient(base_url=api["base"]) as c:
            m = (await c.get("/metrics")).text
            assert "vlog_stage_duration_seconds_bucket" in m
            assert "vlog_rung_duration_seconds_bucket" in m
            # the ingested (fleet) twins: proves the spans endpoint fed
            # the server-side histograms — these are a separate family
            # from the worker's own observations so scraping both
            # endpoints never double-counts a run
            assert "vlog_fleet_stage_duration_seconds_bucket" in m
            assert "vlog_fleet_rung_duration_seconds_bucket" in m
            assert 'vlog_jobs{state="completed"} 1' in m
            assert "vlog_job_backoff_total" in m
            assert "vlog_breaker_transitions_total" in m
            assert "vlog_gc_runs_total" in m
            assert "vlog_spans_recorded_total" in m

    run(check())
    run(admin.close())


def test_spans_endpoint_requires_claim(run, db, tmp_path, api):
    src = make_y4m(tmp_path / "s.y4m", n_frames=6, width=64, height=48)
    video = run(vids.create_video(db, "Gated", source_path=str(src)))
    run(claims.enqueue_job(db, video["id"]))
    job = run(db.fetch_one("SELECT * FROM jobs WHERE video_id=:v",
                           {"v": video["id"]}))
    from vlog_tpu.worker.remote import ClaimLost

    with pytest.raises(ClaimLost):
        run(api["client"].post_spans(job["id"], [{
            "name": "worker.rogue", "span_id": "ab12", "started_at": 1.0,
            "duration_s": 1.0, "attrs": {}}]))
    assert run(db.fetch_all(
        "SELECT * FROM job_spans WHERE job_id=:j AND origin='worker'",
        {"j": job["id"]})) == []


def test_worker_health_port_exposes_metrics(run):
    """The new /metrics on WorkerHealthServer serves the runtime
    registry — workers exported nothing before this route."""
    import socket

    from vlog_tpu.worker.health import WorkerHealthServer

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    async def go():
        import httpx

        async def ready():
            return True, "ok"

        health = WorkerHealthServer(ready, port=port, host="127.0.0.1")
        assert await health.start()
        try:
            async with httpx.AsyncClient(
                    base_url=f"http://127.0.0.1:{port}") as c:
                text = (await c.get("/metrics")).text
                assert "vlog_stage_duration_seconds" in text
                assert "vlog_worker_jobs_total" in text
                assert "vlog_breaker_state" in text
                assert (await c.get("/health")).status_code == 200
        finally:
            await health.stop()

    run(go())


# --------------------------------------------------------------------------
# Failpoint-induced failure -> error-tagged span (daemon path)
# --------------------------------------------------------------------------

def test_failpoint_failure_produces_error_span(run, db, tmp_path):
    from vlog_tpu.worker.daemon import WorkerDaemon

    video = run(vids.create_video(db, "Chaos",
                                  source_path=str(tmp_path / "none.y4m")))
    run(claims.enqueue_job(db, video["id"]))
    daemon = WorkerDaemon(db, name="chaos-w", backend=None,
                          video_dir=tmp_path / "out")
    failpoints.arm("daemon.compute", count=1)
    try:
        assert run(daemon.poll_once()) is True
    finally:
        failpoints.reset()
    job = run(db.fetch_one("SELECT * FROM jobs WHERE video_id=:v",
                           {"v": video["id"]}))
    errs = run(db.fetch_all(
        "SELECT * FROM job_spans WHERE job_id=:j AND status='error'",
        {"j": job["id"]}))
    assert errs, "failpoint failure left no error-tagged span"
    names = {r["name"] for r in errs}
    assert "worker.attempt" in names      # daemon-side, origin worker
    assert "job.fail" in names            # claims-side marker
    attempt = next(r for r in errs if r["name"] == "worker.attempt")
    assert attempt["origin"] == "worker"
    assert "failpoint" in attempt["attributes"]
    # the armed fire was counted per site in the runtime registry
    assert 'vlog_failpoint_fires_total{site="daemon.compute"}' \
        in runtime().render_text()


# --------------------------------------------------------------------------
# Scrape cost + route-label cardinality
# --------------------------------------------------------------------------

def test_metrics_render_aggregates_in_sql(run, db):
    async def seed():
        for i, title in enumerate(["a", "b", "c"]):
            v = await vids.create_video(db, title)
            await claims.enqueue_job(db, v["id"])
        await claims.claim_job(db, "w1")

    run(seed())
    text = run(Metrics().render(db))
    assert 'vlog_jobs{state="claimed"} 1' in text
    assert 'vlog_jobs{state="unclaimed"} 2' in text
    assert "vlog_jobs_queued 2" in text
    # the scrape must stay O(states): no full-table read into Python
    src = Path(Metrics.render.__code__.co_filename).read_text()
    assert "SELECT * FROM jobs" not in src


def test_unmatched_routes_collapse_to_one_label(run, db, tmp_path):
    app = build_worker_app(db, video_dir=tmp_path / "v")
    server = TestServer(app)
    run(server.start_server())
    import httpx

    async def go():
        async with httpx.AsyncClient(base_url=str(server.make_url(""))) as c:
            await c.get("/totally/bogus/path-1")
            await c.get("/totally/bogus/path-2")
            text = (await c.get("/metrics")).text
            assert 'route="unmatched"' in text
            assert "bogus" not in text

    run(go())
    run(server.close())


# --------------------------------------------------------------------------
# Previously write-only surfaces now feed the registry
# --------------------------------------------------------------------------

def test_breaker_transitions_counted():
    from vlog_tpu.worker.breaker import CircuitBreaker

    clock = [0.0]
    br = CircuitBreaker(failure_threshold=1, cooldown_s=10.0,
                        clock=lambda: clock[0])
    br.record_failure()                      # -> open
    clock[0] = 20.0
    assert br.allow()                        # -> half_open
    br.record_success()                      # -> closed
    text = runtime().render_text()
    for state in ("open", "half_open", "closed"):
        assert f'vlog_breaker_transitions_total{{state="{state}"}}' in text
    assert "vlog_breaker_state 0.0" in text


def test_alert_metrics_wired():
    from vlog_tpu.jobs.alerts import AlertSink

    sink = AlertSink(url="http://example.invalid/hook", min_interval_s=600)
    assert sink._allowed("k") is True
    assert sink._allowed("k") is False       # suppressed
    assert sink.metrics.suppressed == 1
    assert 'vlog_alerts_total{outcome="suppressed"}' \
        in runtime().render_text()


def test_daemon_stats_wired():
    from vlog_tpu.worker.daemon import DaemonStats

    stats = DaemonStats()
    stats.bump("claimed")
    stats.bump("completed")
    assert (stats.claimed, stats.completed) == (1, 1)
    text = runtime().render_text()
    assert 'vlog_worker_jobs_total{event="claimed"}' in text
    assert 'vlog_worker_jobs_total{event="completed"}' in text


# --------------------------------------------------------------------------
# Registry / docs agreement (the "new planes can't ship blind" lint) —
# declared coverage lives here; extraction/docs mechanics live once in
# vlog_tpu.analysis.registry, shared with the static-analysis gate.
# --------------------------------------------------------------------------

class TestObservabilityAgreement:
    OBS_KNOBS = ("VLOG_TRACE_ENABLED", "VLOG_WORKER_HEALTH_PORT")
    # span names every docs/dashboard consumer may rely on
    SPAN_NAMES = ("queue.wait", "server.claim", "server.complete",
                  "worker.download", "worker.attempt", "worker.transcode",
                  "worker.upload", "job.complete", "job.fail")

    def test_every_metric_family_documented(self):
        from vlog_tpu.analysis import registry as reg

        names = reg.metric_families(reg.repo_modules())
        assert names, "metric extraction produced no families"
        reg.assert_metric_families(names)

    def test_every_failpoint_site_has_metric_and_docs(self):
        """Each SITES entry must be countable (the labeled fires
        counter observes every site by construction — assert the hook
        actually fires) and documented."""
        from vlog_tpu.analysis import registry as reg

        reg.assert_failpoint_sites(failpoints.SITES)
        m = runtime()
        failpoints.arm("claims.claim", count=1)
        try:
            with pytest.raises(failpoints.FailpointError):
                failpoints.hit("claims.claim")
        finally:
            failpoints.reset()
        assert 'vlog_failpoint_fires_total{site="claims.claim"}' \
            in m.render_text()

    def test_obs_knobs_parsed_and_documented(self):
        from vlog_tpu.analysis import registry as reg

        reg.assert_knobs(self.OBS_KNOBS)
        assert isinstance(config.TRACE_ENABLED, bool)

    def test_stage_and_span_names_documented(self):
        from vlog_tpu.analysis import registry as reg

        stage_names = [f"stage.{key[:-2]}" for key in obs_trace.STAGE_KEYS]
        reg.assert_span_names(tuple(stage_names) + self.SPAN_NAMES)


# --------------------------------------------------------------------------
# Named scopes: what a device trace is read by (obs/profiler.summarize)
# --------------------------------------------------------------------------

_BEAM_SCOPES = {"asr.encoder", "asr.encoder.conv", "asr.encoder.attn",
                "asr.encoder.mlp", "asr.cross_kv", "asr.prompt",
                "asr.token_rules", "asr.beam_select", "asr.beam_reorder",
                "asr.beam_ancestry", "asr.decoder_step",
                "asr.decoder_step.self_attn", "asr.decoder_step.cache_update",
                "asr.decoder_step.cross_attn", "asr.decoder_step.mlp",
                "asr.decoder_step.logits", "asr.beam_final"}
ASR_SCOPES = {
    "beam": _BEAM_SCOPES,
    "beam1": _BEAM_SCOPES,      # a beam of one is the same program
    "mel": {"asr.mel"},
    "ladder": {"ladder.resize", "ladder.intra", "ladder.motion_search",
               "ladder.mc", "ladder.transform_quant", "ladder.deblock",
               "ladder.bitproxy"},
}


def _scopes_in(jaxpr, prefix: str = "") -> set[str]:
    """Every ``asr.*`` / ``ladder.*`` scope in the name stacks of a
    jaxpr's equations, those of nested jaxprs (scan bodies, inner jits)
    under their equation's own stack."""
    import re

    found: set[str] = set()
    for eqn in jaxpr.eqns:
        here = "/".join(p for p in (prefix, str(eqn.source_info.name_stack))
                        if p)
        found |= set(re.findall(
            r"(?<![A-Za-z0-9_.])((?:asr|ladder)\.[A-Za-z0-9_.]+)", here))
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found |= _scopes_in(inner, here)
    return found


@pytest.mark.parametrize("program", sorted(ASR_SCOPES))
def test_programs_carry_their_named_scopes(program):
    """Tiny widths, nothing runs: the scopes are in the name stacks of
    the generate program (at beam 3 and at a beam of one), the mel
    program and the chain ladder (scan
    bodies included), and the program names nothing else ``asr.*`` or
    ``ladder.*`` (a typo would read as a scope of its own in a trace)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if program == "ladder":
        from vlog_tpu.parallel.ladder import ladder_chain_program

        fn, mats = ladder_chain_program((("r0", 32, 48, 30),), 64, 96,
                                        search=4, mesh=None, deblock=True)
        y = np.zeros((1, 3, 64, 96), np.uint8)
        c = np.zeros((1, 3, 32, 48), np.uint8)
        jaxpr = jax.make_jaxpr(fn)(
            y, c, c, mats, {"r0": np.full((1, 3), 30, np.int32)},
            {"r0": {"budget": np.float32(100.0),
                    "alpha": np.float32(0.1)}})
    elif program == "mel":
        from vlog_tpu.asr.mel import log_mel_spectrogram

        jaxpr = jax.make_jaxpr(log_mel_spectrogram)(
            np.zeros((1, 16000), np.float32))
    else:
        from vlog_tpu.asr import decode
        from vlog_tpu.asr.model import (DecoderCache, WhisperConfig,
                                        init_random_params)

        cfg = WhisperConfig(
            d_model=32, encoder_layers=1, decoder_layers=1,
            encoder_attention_heads=2, decoder_attention_heads=2,
            encoder_ffn_dim=64, decoder_ffn_dim=64, vocab_size=120,
            max_source_positions=50, max_target_positions=16)
        beam = 3 if program == "beam" else 1
        kw = dict(cfg=cfg, sot=100, eot=99, ts_begin=110, no_speech=105,
                  max_new=4, timestamps=True, beam=beam)
        jaxpr = jax.make_jaxpr(
            lambda *a: decode._generate_beam_jit(*a, **kw))(
            init_random_params(cfg), jnp.zeros((2, 80, 100)),
            jnp.asarray([100, 101, 102], jnp.int32), jnp.zeros(120),
            jnp.zeros(120), DecoderCache.create(cfg, 2 * beam, 7))
    assert _scopes_in(jaxpr.jaxpr) == ASR_SCOPES[program]


# --------------------------------------------------------------------------
# The build meter: which thread rebuilt, and what the rebuild was
# --------------------------------------------------------------------------

def test_build_meter_books_by_thread_and_phase():
    """A jit first run on a named thread shows under that thread's name
    as trace, lower and compile seconds; the same call again adds
    nothing (that is how a tick record's ``build_s`` reads 0)."""
    import jax
    import jax.numpy as jnp

    from vlog_tpu.parallel import compile_cache as cc

    cc.build_seconds()                    # first use arms the listener
    compiled_before = cc.compile_seconds()
    name = f"vlog-test-builder-{time.monotonic_ns()}"

    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.0 + 1.0

    def work():
        fresh(jnp.ones(7)).block_until_ready()

    for _ in range(2):
        t = threading.Thread(target=work, name=name)
        t.start()
        t.join(60.0)
        assert not t.is_alive()
        if _ == 0:
            first = cc.build_seconds()[name]
    assert set(first) == {"trace", "lower", "compile", "cache_load"}
    assert first["trace"] > 0.0 and first["lower"] > 0.0 \
        and first["compile"] > 0.0
    assert cc.build_seconds()[name] == first
    assert cc.build_total(first) == pytest.approx(
        first["trace"] + first["lower"] + first["compile"])
    # the calling thread's own entry, zeros where it built nothing
    mine = cc.thread_build_seconds()
    assert set(mine) == set(first) and mine == cc.build_seconds().get(
        threading.current_thread().name, dict.fromkeys(first, 0.0))
    # compile_seconds() keeps its meaning: backend compile, process-wide
    assert cc.compile_seconds() - compiled_before >= first["compile"] - 1e-9


def test_build_meter_books_a_nested_trace_once(monkeypatch):
    """jax reports a jit traced inside another's trace on its own and
    again inside the outer event; the outer event is booked without
    it. (On a thread of its own: the meter keeps each thread's open
    traces apart, and this one's clock is made up.)"""
    from vlog_tpu.parallel import compile_cache as cc

    clock = [100.0]
    trace_event = next(e for e, p in cc.BUILD_PHASES.items() if p == "trace")
    name = f"vlog-test-nested-{time.monotonic_ns()}"

    def events():
        for now, duration in ((100.4, 0.3),     # inner: [100.1, 100.4]
                              (100.8, 0.3),     # sibling: [100.5, 100.8]
                              (101.0, 1.0),     # outer: holds both
                              (102.0, 0.5)):    # a later top-level one
            clock[0] = now
            cc._on_event_duration(trace_event, duration)
        cc._on_event_duration("/jax/some/other/event", 9.0)

    cc.build_seconds()                          # listener armed
    monkeypatch.setattr(cc, "_now", lambda: clock[0])
    t = threading.Thread(target=events, name=name)
    t.start()
    t.join(10.0)
    assert not t.is_alive()
    booked = cc.build_seconds()[name]
    assert booked["trace"] == pytest.approx(1.5)
    assert booked["lower"] == booked["compile"] == 0.0
