"""The step engine and its two-class page pool on the CPU: every page
comes back, window rings keep to their bound, admission waits instead of
evicting, the step record adds up, and the engine swaps residency with
the ASR plane at a job boundary."""

# slowlane-ok(module): the tiny model's step programs build in seconds
import gc
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


def test_the_step_record_says_who_kept_the_chip_waiting(model):
    """PR 37: every step carries the pull's wait record, the collection
    seconds of its own stretch, its stall cause and ``idle_before_s``
    (None with no step in flight, else from the first of the plan's
    checks that found it done to the dispatch); ``stats()["waits"]``
    counts every pull and is still there after ``close()``."""
    _hf, cfg, params = model
    eng = engine(cfg, params, rows=4)
    try:
        for r in [eng.submit(np.arange(n) % 512, max_new=5)
                  for n in (30, 9, 17)]:
            r.wait(300)
        log = list(eng.step_log)
    finally:
        eng.close()
    stats = eng.stats()
    for rec in log:
        wait = rec["wait"]
        assert set(wait) == {"polls", "gap_max_s", "cpu_s", "gc_s", "wait_s",
                         "ready_max_s", "copy_s"}
        assert wait["polls"] >= 1
        assert 0.0 <= wait["gap_max_s"] <= wait["wait_s"]
        assert wait["wait_s"] <= rec["phase_s"]["device_wait"] + 1e-6
        assert 0.0 <= rec["gc_s"] <= rec["t_end"] - rec["t_start"]
        assert rec["stall"] in (None, "gc", "host", "runtime")
        idle = rec["idle_before_s"]
        assert idle is None or 0.0 <= idle <= rec["t_dispatch"] \
            - rec["t_start"]
    assert log[0]["idle_before_s"] is None      # nothing was in flight
    assert any(rec["idle_before_s"] is not None for rec in log)
    waits = stats["waits"]
    assert waits["count"] == len(log)
    assert len(waits["longest"]) == min(5, len(log))
    assert waits["longest"][0]["wait_s"] == max(
        rec["wait"]["wait_s"] for rec in log)
    assert {w["key"] for w in waits["longest"]} <= set(
        eng.geo.chunk_buckets())
    assert sum(waits["stalls"].values()) == sum(
        rec["stall"] is not None for rec in log)


def test_idle_before_is_zero_while_the_step_in_flight_runs(model):
    """The plan's checks (``_probe``) ask the step in flight; one that
    never reads ready leaves ``idle_before_s`` at 0.0, one that reads
    ready at the first check makes it run from there."""
    from vlog_tpu.lm.engine import LmEngine
    from vlog_tpu.lm.load import LmAssets

    _hf, cfg, params = model
    # not started: no thread plans beside the test
    eng = LmEngine(LmAssets(cfg, params, None, "tiny"),
                   geometry=geometry(cfg, rows=4))
    try:
        class Busy:
            def __init__(self, ready):
                self.ready = ready
                self.calls = 0

            def is_ready(self):
                self.calls += 1
                return self.ready

        busy = Busy(False)
        eng._flight = ({}, {"ints": busy})
        for _ in range(4):
            eng._probe()
        assert eng._idle_from is None and busy.calls == 4
        done = Busy(True)
        eng._flight = ({}, {"ints": done})
        eng._probe()
        eng._probe()
        assert eng._idle_from is not None and done.calls == 1
    finally:
        eng.close()


def test_an_engine_thread_starting_installs_the_recorder(model, monkeypatch):
    """The process's GC recorder goes on when an engine's thread starts,
    once a process, also for an engine built without the registry (as
    the benchmark builds them)."""
    from vlog_tpu.lm.engine import LmEngine
    from vlog_tpu.lm.load import LmAssets
    from vlog_tpu.obs import hostwait, trace

    _hf, cfg, params = model
    monkeypatch.setattr(trace, "start_thread", lambda *a, **k: None)
    was = hostwait.GC._installed
    hostwait.GC.reset()
    engines = [LmEngine(LmAssets(cfg, params, None, "tiny"),
                        geometry=geometry(cfg, rows=4)) for _ in range(2)]
    try:
        assert hostwait.GC._callback not in gc.callbacks
        for eng in engines:
            eng._start_locked()
        assert gc.callbacks.count(hostwait.GC._callback) == 1
    finally:
        for eng in engines:
            eng.close()
        hostwait.GC.reset()
        if was:
            hostwait.GC.install()


@pytest.mark.parametrize("family", ["afmoe", "keye", "xing"])
def test_the_step_record_says_which_form_the_chunk_attended_in(model,
                                                               family):
    """``attn_chunk_form`` and ``attn_select_form`` are fixed when the
    bucket's program is built (``model.py::attention_form``,
    ``select_form``): on this backend every chunk attends and a model
    with an indexer chooses its keys in the loop; a step without a chunk
    has neither, nor has a model without an indexer a choice."""
    from lm_helpers import tiny_keye, tiny_xing

    _hf, cfg, params = {"afmoe": lambda: model, "keye": tiny_keye,
                        "xing": tiny_xing}[family]()
    eng = engine(cfg, params, rows=4)
    try:
        for r in [eng.submit(np.arange(n) % 512, max_new=4)
                  for n in (21, 5)]:
            r.wait(300)
        log, stats = list(eng.step_log), eng.stats()
        chunk_form = "latent_expanded_loop" if family == "xing" else "loop"
        assert eng.attn_forms == {b: chunk_form if b else None
                                  for b in eng.geo.chunk_buckets()}
        choice = "loop" if family == "keye" else None
        assert eng.select_forms == {b: choice if b else None
                                    for b in eng.geo.chunk_buckets()}
    finally:
        eng.close()
    with_chunk = [rec for rec in log if rec["chunk"]]
    assert with_chunk and len(with_chunk) < len(log)
    assert {rec["attn_chunk_form"] for rec in with_chunk} == {chunk_form}
    assert {rec["attn_select_form"] for rec in with_chunk} == {choice}
    assert all(rec["attn_chunk_form"] is None
               and rec["attn_select_form"] is None
               for rec in log if not rec["chunk"])
    counts = {"kernel_steps": 0, "loop_steps": 0,
              f"{chunk_form}_steps": len(with_chunk)}
    if choice:      # the choices add up to the chunk steps
        counts["select_loop_steps"] = len(with_chunk)
    assert stats["attn"] == counts


def test_keyes_engine_serves_the_same_tokens_with_the_choice_kernel(
        monkeypatch):
    """With ``select_form`` steered to the kernel wherever Mosaic would
    tile the bucket (Pallas's interpreter in the chip's place; pages of
    32 in blocks of 128 keys, so buckets of 32 and 64 queries take it),
    every chunk step records it and the engine serves the tokens the
    loop serves: prompts over the 16 keys kept, across blocks."""
    import functools

    from lm_helpers import tiny_keye

    from vlog_tpu.lm import attention_kernel
    from vlog_tpu.lm import model as lm_model

    _hf, cfg, params = tiny_keye()
    prompts = [np.arange(n) % 512 for n in (150, 40, 300)]

    def served():
        eng = engine(cfg, params, rows=4, chunk=64, page=32, block=4,
                     cap=512)
        try:
            tokens = [r.wait(600) for r in [
                eng.submit(p, max_new=6) for p in prompts]]
            return tokens, list(eng.step_log), eng.stats()
        finally:
            eng.close()

    want, log, stats = served()
    chunks = sum(1 for rec in log if rec["chunk"])
    assert stats["attn"]["select_loop_steps"] == chunks
    monkeypatch.setattr(lm_model, "select_form", lambda nq, width, block: (
        "kernel" if attention_kernel.select_supported(nq, width, block)
        else "loop"))
    monkeypatch.setattr(
        attention_kernel, "select_keys_kernel", functools.partial(
            attention_kernel.select_keys_kernel, interpret=True))
    got, log, stats = served()
    assert {rec["attn_select_form"] for rec in log if rec["chunk"]} \
        == {"kernel"}
    assert stats["attn"]["select_kernel_steps"] == chunks
    assert got == want


@pytest.mark.parametrize("form", ["loop", "rows_kernel"])
def test_the_step_record_says_which_form_the_rows_attended_in(
        monkeypatch, model, form):
    """``attn_rows_form`` is what ``build_step`` chose for the rows
    (``model.py::attention_form``): the loop on this backend. With the
    choice steered to the rows' kernel (Pallas's interpreter in the
    chip's place) every record and ``stats()`` say so, and the engine
    serves the same tokens: window and full layers, rows of ragged
    length, rows that come and go."""
    import functools

    from vlog_tpu.lm import attention_kernel
    from vlog_tpu.lm import model as lm_model

    _hf, cfg, params = model
    prompts = [np.arange(n) % 512 for n in (21, 5, 40)]

    def served():
        eng = engine(cfg, params, rows=4)
        try:
            tokens = [r.wait(300) for r in [
                eng.submit(p, max_new=6) for p in prompts]]
            return tokens, list(eng.step_log), eng.stats()
        finally:
            eng.close()

    want, log, stats = served()
    assert {rec["attn_rows_form"] for rec in log} == {"loop"}
    assert stats["attn_rows_form"] == "loop"
    if form == "loop":
        return
    choose = lm_model.attention_form

    def steered(seqs, nq, *shape, chosen=False, expand=False):
        if nq == 1 and not chosen and not expand:
            return "rows_kernel"
        return choose(seqs, nq, *shape, chosen=chosen, expand=expand)

    monkeypatch.setattr(lm_model, "attention_form", steered)
    monkeypatch.setattr(
        attention_kernel, "rows_attention", functools.partial(
            attention_kernel.rows_attention, interpret=True))
    got, log, stats = served()
    assert {rec["attn_rows_form"] for rec in log} == {"rows_kernel"}
    assert stats["attn_rows_form"] == "rows_kernel"
    assert got == want


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_step_record_says_which_form_the_experts_combined_in(
        monkeypatch, form):
    """``moe_combine_form`` is what ``build_step`` chose for the
    experts' combine (``moe.py::combine_form``): XLA on this backend.
    With the choice steered to the kernel (Pallas's interpreter in the
    chip's place) every record and ``stats()`` say so, and the engine
    serves the same tokens: a model that holds half its router's
    experts, so that about half the pairs are neither fetched nor
    added, in every bucket."""
    import functools

    from lm_helpers import tiny_qwen

    from vlog_tpu.lm import moe, moe_kernel

    _hf, cfg, params = tiny_qwen(hidden_size=128)
    prompts = [np.arange(n) % 512 for n in (21, 5, 40)]

    def served():
        eng = engine(cfg, params, rows=8, chunk=16, page=8, cap=128)
        try:
            tokens = [r.wait(300) for r in [
                eng.submit(p, max_new=6) for p in prompts]]
            return tokens, list(eng.step_log), eng.stats()
        finally:
            eng.close()

    want, log, stats = served()
    assert {rec["moe_combine_form"] for rec in log} == {"xla"}
    assert (stats["combine_xla_steps"], stats["combine_kernel_steps"]) \
        == (len(log), 0)
    if form == "xla":
        return
    monkeypatch.setattr(moe, "combine_form", lambda t, k, h: (
        "kernel" if moe_kernel.supported(t, k, h) else "xla"))
    monkeypatch.setattr(moe, "combine", functools.partial(
        moe.combine, interpret=True))
    got, log, stats = served()
    assert {rec["moe_combine_form"] for rec in log} == {"kernel"}
    assert (stats["combine_xla_steps"], stats["combine_kernel_steps"]) \
        == (0, len(log))
    assert got == want


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


# ---- a model whose every layer is of the full class ---------------------

def test_a_pool_smaller_than_the_rows_finishes_every_request_and_counts():
    """Four rows, a pool that holds two of the longest requests: the
    pool, not the rows, bounds what is resident. Every request finishes,
    rows stand empty while the next in line waits for pages, and the
    plan, the step record and the counters say so."""
    from lm_helpers import tiny_keye

    _hf, cfg, params = tiny_keye()
    # 40 + 8 positions = 12 pages a request; 25 pages hold two
    eng = engine(cfg, params, rows=4, chunk=16, page=4, cap=64,
                 full_pages=26)
    rng = np.random.default_rng(2)
    try:
        assert eng.geo.window_pages == 0
        reqs = [eng.submit(rng.integers(0, cfg.vocab_size, 40), max_new=8)
                for _ in range(7)]
        for r in reqs:
            assert len(r.wait(300)) == 8
        for _ in range(100):
            if eng.stats()["pages_in_use"]["full"] == 0:
                break
            threading.Event().wait(0.05)
        stats = eng.stats()
        log = list(eng.step_log)
    finally:
        eng.close()
    assert stats["requests_done"] == 7
    assert stats["pool"]["full"] == {"capacity": 25, "in_use": 0,
                                     "reserved": 0}
    assert stats["pool"]["window"] == {"capacity": 0, "in_use": 0,
                                       "reserved": 0}
    waited = [rec for rec in log if rec["pool_wait_rows"]]
    assert waited and stats["pool_wait"] == {
        "steps": len(waited),
        "rows": sum(rec["pool_wait_rows"] for rec in waited)}
    for rec in log:
        # never more resident than the pool holds, whatever the rows
        assert rec["decode_rows"] <= 2 and rec["pages_in_use"]["full"] <= 25
        assert rec["pages_in_use"]["window"] == 0
        # at least two rows were free whenever a request waited for
        # pages (fewer are counted once fewer requests wait)
        assert rec["pool_wait_rows"] <= 2
    # with the pool at every row's need nothing waits
    eng = engine(cfg, params, rows=4, chunk=16, page=4, cap=64)
    try:
        for r in [eng.submit(np.arange(40), max_new=8) for _ in range(6)]:
            r.wait(300)
        assert eng.stats()["pool_wait"] == {"steps": 0, "rows": 0}
        assert max(rec["decode_rows"] for rec in eng.step_log) >= 3
    finally:
        eng.close()


def test_a_request_the_pool_can_never_hold_is_refused(model):
    from lm_helpers import tiny_keye

    _hf, cfg, params = tiny_keye()
    eng = engine(cfg, params, rows=2, chunk=16, page=4, cap=64,
                 full_pages=9)
    try:
        with pytest.raises(LmJobError, match="exceed the cache"):
            eng.submit(np.zeros(40, np.int32), max_new=4).wait(60)
        assert len(eng.submit(np.zeros(20, np.int32), max_new=4)
                   .wait(60)) == 4
    finally:
        eng.close()


def test_the_default_pools_follow_the_models_layers(model):
    """Every row its longest request where that fits the budget; a model
    whose every layer is of the full class gets the pages that fit, and
    one without window layers no window pool."""
    import json as _json
    from pathlib import Path

    from vlog_tpu.lm import engine as lm_engine
    from vlog_tpu.lm.model import LmConfig

    _hf, afmoe, _params = model
    geo = lm_engine.default_geometry(afmoe)
    assert geo.window_pages == 32 * geo.ring(afmoe.sliding_window) + 1
    assert geo.full_pages == 32 * geo.max_pages + 1
    configs = Path(__file__).resolve().parents[1] / "benchmark" / "configs"
    keye = LmConfig.from_hf(_json.loads(
        (configs / "keye_vl2_lm_6l.json").read_text()))
    assert keye.position_bytes() == (0, 13_056)
    geo = lm_engine.default_geometry(keye)
    geo.check(keye)
    assert geo.window_pages == 0
    assert geo.full_pages - 1 == lm_engine.POOL_BYTES // (256 * 13_056) \
        == 1285 < 32 * geo.max_pages
    trinity = LmConfig.from_hf(_json.loads(
        (configs / "trinity_mini_6l.json").read_text()))
    geo = lm_engine.default_geometry(trinity)
    assert (geo.window_pages, geo.full_pages) == (513, 5121)


def test_a_digest_runs_the_second_family_from_its_model_directory(
        monkeypatch, tmp_path):
    """``config.json`` names the family; the same loader, engine and
    ``digest_tokens`` serve it, and no environment variable chooses."""
    from lm_helpers import keye_ref, tiny_keye

    from vlog_tpu.lm import engine as lm_engine
    from vlog_tpu.lm import load
    from vlog_tpu.worker.digest import digest_tokens

    hf, _cfg, params = tiny_keye()
    model_dir = save_model_dir(tmp_path / "keye", hf, params, shards=2)
    assets = load.load_model_dir(model_dir)
    assert assets.cfg.model_type == "KeyeVL2" and assets.cfg.index_topk == 16
    assert sorted(assets.params["layers"][0]) == sorted(
        name for name, _shape, _kind in load.layer_leaves(assets.cfg, 0))
    monkeypatch.setattr(lm_engine, "default_geometry", lambda cfg: geometry(
        cfg, rows=2, chunk=16, page=4, cap=64))
    try:
        eng = lm_engine.get_engine(str(model_dir))
        stats: dict = {}
        prompt = np.arange(30) % 256
        req = digest_tokens(eng, prompt, max_new=4, job_key="k",
                            capture=(0, 3), stats_out=stats)
        assert stats["prompt_tokens"] == 30 and stats["output_tokens"] == 4
        scopes = set().union(*(set(v.values())
                               for v in eng.program_scopes().values()))
    finally:
        lm_engine.reset_engine()
    assert {"lm.attn.index", "lm.attn.select", "lm.attn.sparse",
            "lm.cache.write", "lm.moe.experts"} <= scopes
    assert not {"lm.attn.window", "lm.attn.full", "lm.attn.gate"} & scopes
    full = np.concatenate([prompt, req.tokens[:-1]]).astype(np.int32)
    out = keye_ref.forward(params, hf, full, [29, 32])
    for row, step in enumerate((0, 3)):
        assert keye_ref.logit_error(req.logits[step], out["logits"][row]) \
            < 1.2
    # a tensor under another name is refused by that name
    sd = load._read_weights(model_dir)
    sd.pop("model.layers.1.self_attn.indexer.wk.weight")
    with pytest.raises(load.LmLoadError, match="indexer.wk.weight"):
        load.from_state_dict(assets.cfg, sd)
