"""The per-layer metrics that read what the program records of itself
(PR 26): the engine's tick record, the entry's ``stats_out`` and the
build meter. CPU; a reader's number here is a reading of a rehearsal,
never a device number."""

import json
import sys
import threading
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark_proc import BENCH, CELLS, ROOT, last_line, run

BENCH_DIR = ROOT / "benchmark"
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

from harness import spec  # noqa: E402

NEW = {"asr_tick_gap_ms.backlog": "program_span",
       "asr_device_wait_ms.backlog": "program_span",
       "asr_language_pass_ms.backlog": "program_span",
       "asr_engine_build_s": "program_counter"}


def test_the_four_entries_keep_to_the_contract():
    bench = spec.load_bench()
    assert spec.check_names(bench) == []
    entries = {m["name"]: m for m in bench["per_layer"]}
    # the layers' names as the metric files of PR 25 spell them (the
    # clips cell's, not yet entered, name the entry's layer)
    layers = {json.loads(f.read_text())["layer"]
              for f in (BENCH_DIR / "layer_metrics").glob("*.json")
              if f.stem not in NEW}
    for name, source in NEW.items():
        m = entries[name]
        assert m["source"] == source and m["better"] == "lower"
        assert m["workloads"] == CELLS[:2]
        assert m["layer"] in layers         # letter for letter
        f = json.loads((BENCH_DIR / "layer_metrics" / f"{name}.json"
                        ).read_text())
        assert callable(spec.plugin("readers", f["reader"]).read)
    # new entries stand at the end, after the five that were there
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(NEW)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """One rehearsal of the first cell through ``records.py``: the result
    line, and what the program recorded of itself."""
    out = tmp_path_factory.mktemp("records") / "rec.json"
    proc = run(["benchmark/records.py", "--out", str(out), "--workload",
                CELLS[0], "--seed", str(2**31 + 26), "--seconds", "4",
                "--trace", "1", "--rehearse"])
    line = last_line(proc)
    kept = json.loads(out.read_text())
    jobs = [SimpleNamespace(stats=j["stats"], start_t=j["began_s"])
            for j in kept["jobs"]]
    ctx = {"batch_log": kept["tick_records"], "jobs": jobs,
           "window": {"t0": 0.0,
                      "t_end": kept["window"]["t_end"]
                      - kept["window"]["t0"]}}
    return line, kept, ctx


def test_a_traced_line_names_the_new_metrics(rehearsed):
    line, kept, _ctx = rehearsed
    assert line["correct"] is True and kept["correct"] is True
    # a rehearsal's queue drains between its few ticks, so every gap of
    # the window may lie across an idle wait; then the line leaves the
    # gap out, as it leaves out a language pass that no job began
    assert {"asr_device_wait_ms.backlog", "asr_engine_build_s"} <= set(
        line["metrics"]) <= set(NEW) | {"asr_occupancy.backlog",
                                        "asr_tick_ms.backlog"}
    assert ("asr_tick_gap_ms.backlog" in line["metrics"]) == any(
        t["gap_s"] is not None for t in kept["tick_records"])
    assert kept["workload"] == CELLS[0]
    assert len(kept["tick_records"]) == line["extra"]["ticks"] >= 1


@pytest.mark.parametrize("reader,args", [
    ("tick_record_ms", {"field": "gap_s"}),
    ("tick_record_ms", {"phase": "device_wait"}),
    ("tick_record_ms", {"phase": "mel"}),
    ("job_stat_ms", {"key": "vad_s"}),
])
def test_reader_gives_a_number_on_a_rehearsed_cell(rehearsed, reader, args):
    _line, kept, ctx = rehearsed
    value = spec.plugin("readers", reader).read(ctx, **args)
    if args == {"field": "gap_s"} and all(
            t["gap_s"] is None for t in kept["tick_records"]):
        assert value is None        # every gap lay across an idle wait
        return
    assert isinstance(value, float) and value >= 0.0
    if args == {"phase": "device_wait"}:
        ticks = kept["tick_records"]
        assert value == pytest.approx(1000.0 * sum(
            t["phase_s"]["device_wait"] for t in ticks) / len(ticks))
        # the wait lies inside the tick's host time
        assert value <= spec.plugin("readers", "tick_ms").read(ctx)


def test_phases_cover_every_cycle_of_the_rehearsal(rehearsed):
    _line, kept, _ctx = rehearsed
    ticks = kept["tick_records"]
    for prev, t in zip(ticks, ticks[1:]):
        if t["gap_s"] is None:
            continue
        cycle = t["t_ready"] - prev["t_ready"]
        named = (prev["phase_s"]["parse"] + prev["phase_s"]["deliver"]
                 + sum(t["phase_s"][p] for p in (
                     "coalesce", "lease", "take", "stack", "mel",
                     "dispatch", "device_wait")))
        assert named <= cycle + 1e-6 and cycle - named < 0.25


@pytest.mark.parametrize("reader,args", [
    ("tick_record_ms", {"field": "gap_s"}),
    ("tick_record_ms", {"phase": "device_wait"}),
    ("job_stat_ms", {"key": "language_pass_s"}),
])
def test_reader_says_nothing_where_the_program_records_nothing(reader, args):
    """The parent's engine: ``batch_log`` with the five old keys, a
    ``stats_out`` without stage seconds. ``None``, never 0."""
    ctx = {"batch_log": [{"rows": 8, "n": 8, "occupancy": 1.0,
                          "jobs": ["a"] * 8, "elapsed_s": 9.4}],
           "jobs": [SimpleNamespace(stats={"windows_live": 3},
                                    start_t=1.0)],
           "window": {"t0": 0.0, "t_end": 50.0}}
    assert spec.plugin("readers", reader).read(ctx, **args) is None
    ctx["batch_log"] = []
    ctx["jobs"] = []
    assert spec.plugin("readers", reader).read(ctx, **args) is None


def test_first_ticks_gap_is_left_out_not_read_as_zero():
    read = spec.plugin("readers", "tick_record_ms").read
    log = [{"gap_s": None, "phase_s": {"mel": 0.1}},
           {"gap_s": 0.2, "phase_s": {"mel": 0.3}}]
    assert read({"batch_log": log}, field="gap_s") == pytest.approx(200.0)
    assert read({"batch_log": log}, phase="mel") == pytest.approx(200.0)
    assert read({"batch_log": log[:1]}, field="gap_s") is None
    with pytest.raises(ValueError):
        read({"batch_log": log})
    with pytest.raises(ValueError):
        read({"batch_log": log}, field="gap_s", phase="mel")


def test_job_stat_counts_only_jobs_that_began_inside_the_window():
    read = spec.plugin("readers", "job_stat_ms").read
    jobs = [SimpleNamespace(stats={"language_pass_s": 4.0}, start_t=-3.0),
            SimpleNamespace(stats={"language_pass_s": 20.0}, start_t=9.0),
            SimpleNamespace(stats={"language_pass_s": 30.0}, start_t=19.0),
            SimpleNamespace(stats={"vad_s": 0.1}, start_t=40.0)]
    ctx = {"jobs": jobs, "window": {"t0": 0.0, "t_end": 57.0}}
    assert read(ctx, key="language_pass_s") == pytest.approx(25_000.0)


def test_engine_build_reads_the_engine_threads_meter(monkeypatch):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from vlog_tpu.parallel import compile_cache

    read = spec.plugin("readers", "engine_build_s").read
    monkeypatch.setattr(compile_cache, "build_seconds", lambda: {
        "vlog-asr-engine": {"trace": 5.0, "lower": 3.0, "compile": 1.5,
                            "cache_load": 1.25},
        "bench-client-0": {"trace": 9.0, "lower": 9.0, "compile": 9.0,
                           "cache_load": 0.0}})
    assert read({}) == pytest.approx(9.5)       # cache_load is in compile
    monkeypatch.setattr(compile_cache, "build_seconds", lambda: {})
    assert read({}) is None                     # no engine thread built
    monkeypatch.delattr(compile_cache, "build_seconds")
    assert read({}) is None                     # a program without it
    assert threading.current_thread().name != "vlog-asr-engine"
