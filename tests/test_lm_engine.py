"""The step engine and its two-class page pool on the CPU: every page
comes back, window rings keep to their bound, admission waits instead of
evicting, the step record adds up, and the engine swaps residency with
the ASR plane at a job boundary."""

# slowlane-ok(module): the tiny model's step programs build in seconds
import threading

import numpy as np
import pytest

from lm_helpers import engine, geometry, save_model_dir, tiny

from vlog_tpu.lm.cache import PagedCache
from vlog_tpu.lm.engine import PHASES, LmJobError
from vlog_tpu.parallel.engine_host import HOST


@pytest.fixture(scope="module")
def model():
    return tiny()


def test_200_requests_leave_every_page_free(model):
    _hf, cfg, params = model
    eng = engine(cfg, params, rows=6, chunk=8, page=4, cap=128)
    ring = eng.geo.ring(cfg.sliding_window)
    rng = np.random.default_rng(5)
    try:
        reqs = [eng.submit(rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(1, 70))),
                           max_new=int(rng.integers(1, 7)))
                for _ in range(200)]
        for r in reqs:
            assert len(r.wait(600)) == r.max_new
        # the last rows leave at the next plan, which an idle engine
        # makes within its wait
        deadline = threading.Event()
        for _ in range(100):
            if eng.stats()["pages_in_use"] == {"window": 0, "full": 0}:
                break
            deadline.wait(0.05)
        stats = eng.stats()
        log = list(eng.step_log)
        cache = eng._cache
    finally:
        eng.close()
    assert stats["requests_done"] == 200
    assert stats["pages_in_use"] == {"window": 0, "full": 0}
    assert cache.window.reserved == 0 and cache.full.reserved == 0
    assert all(1 <= r.stats["peak_window_pages"] <= ring for r in reqs)
    # long prompts gave window pages back while they were still running
    assert sum(rec["pages_freed"] for rec in log) > 0
    assert max(rec["pages_in_use"]["window"] for rec in log) \
        <= 6 * ring


def test_admission_waits_for_the_pool_and_refuses_what_never_fits(model):
    _hf, cfg, params = model
    geo = geometry(cfg, rows=4, chunk=8, page=4, cap=64)
    cache = PagedCache(cfg, geo)
    held = [cache.admit(64) for _ in range(4)]
    assert all(h is not None for h in held)
    assert cache.admit(8) is None           # both pools are spoken for
    held[0].extend(40)
    assert held[0].trim(40) > 0             # behind the window: given back
    held[0].release()
    assert cache.admit(8) is not None
    assert not cache.fits_ever(65)
    eng = engine(cfg, params, rows=2, chunk=8, page=4, cap=32)
    try:
        with pytest.raises(LmJobError):
            eng.submit(np.zeros(40, np.int32), max_new=4).wait(60)
        assert len(eng.submit(np.zeros(20, np.int32), max_new=4)
                   .wait(60)) == 4
    finally:
        eng.close()


def test_step_record_adds_up(model):
    _hf, cfg, params = model
    eng = engine(cfg, params, rows=4)
    try:
        reqs = [eng.submit(np.arange(n) % 512, max_new=5)
                for n in (30, 9, 17)]
        for r in reqs:
            r.wait(300)
        log = list(eng.step_log)
    finally:
        eng.close()
    assert [rec["seq"] for rec in log] == list(range(len(log)))
    prefilled = sum(rec["prefill_tokens"] for rec in log)
    emitted = sum(len(rec["emitted"]) for rec in log)
    assert prefilled == 30 + 9 + 17 and emitted == 15
    for rec in log:
        assert set(rec["phase_s"]) == set(PHASES)
        assert rec["t_start"] <= rec["t_dispatch"] <= rec["t_ready"] \
            <= rec["t_end"]
        assert rec["chunk"] in eng.geo.chunk_buckets()
        assert rec["prefill_tokens"] <= max(rec["chunk"], 0)
        assert rec["build_s"] == 0.0        # nothing compiles once warm
        assert len(rec["expert_load"]) == 4
    # at most one request's chunk a step, and rows join after their last
    assert any(rec["decode_rows"] >= 2 for rec in log)
    assert log[0]["gap_s"] is None and all(
        rec["gap_s"] >= 0 for rec in log[1:] if rec["gap_s"] is not None)


def test_eos_ends_a_request_early(model):
    _hf, cfg, params = model
    eng = engine(cfg, params)
    try:
        free = eng.submit(np.arange(12), max_new=10)
        toks = free.wait(300)
        cut = eng.submit(np.arange(12), max_new=10, eos_id=toks[2])
        out = cut.wait(300)
    finally:
        eng.close()
    stop = toks.index(toks[2])
    assert out == toks[:stop + 1]


class _StubEngine:
    """What the host needs of a plane's engine: ``active`` and
    ``close``."""

    def __init__(self):
        self.busy = True
        self.closes = 0

    def active(self):
        return self.busy

    def close(self):
        self.closes += 1


def test_residency_swaps_at_a_job_boundary(model, monkeypatch, tmp_path):
    from vlog_tpu.lm import engine as lm_engine

    hf, _cfg, params = model
    stub = _StubEngine()
    HOST.obtain("stub", "key", lambda: stub)
    assert HOST.obtain("stub", "key", _StubEngine) is stub     # resident
    model_dir = save_model_dir(tmp_path / "m", hf, params, shards=3)
    monkeypatch.setattr(lm_engine, "default_geometry", lambda cfg: geometry(
        cfg, rows=2, chunk=16, page=4, cap=64))
    monkeypatch.setattr(HOST, "room_timeout_s", 0.1)
    monkeypatch.setattr(HOST, "poll_s", 0.01)
    try:
        with pytest.raises(TimeoutError):   # a busy engine is never closed
            lm_engine.get_engine(str(model_dir))
        assert stub.closes == 0 and HOST.active("stub")
        assert lm_engine.peek_engine() is None
        monkeypatch.setattr(HOST, "room_timeout_s", 30.0)
        threading.Timer(0.1, lambda: setattr(stub, "busy", False)).start()
        eng = lm_engine.get_engine(str(model_dir))
        assert stub.closes == 1 and HOST.peek("stub") is None
        assert lm_engine.get_engine(str(model_dir)) is eng
        assert eng.geo.rows == 2 and eng.geo.page == 4
        assert len(eng.submit(np.arange(10), max_new=3).wait(300)) == 3
        # and the reverse: an idle transcript engine makes room
        other = HOST.obtain("stub", "key", _StubEngine)
        assert other.closes == 0 and lm_engine.peek_engine() is None
        assert not HOST.active("lm")
    finally:
        HOST.evict("stub")
        lm_engine.reset_engine()
