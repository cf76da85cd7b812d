"""PR 37's three per-layer metrics, which read who kept the chip waiting
(``vlog_tpu/obs/hostwait.py``): ``lm_host_idle_pct`` (the device idle the
LM engine's plan caused, ``idle_before_s``), ``host_gc_ms_per_s`` (the
process's collections, ``gc_s``) and ``host_pause_max_ms`` (the longest
stretch the waiting thread was away, the wait record's ``gap_max_s``).
Their entries, their readers on synthetic records, and a rehearsal of
one LM cell and one Whisper cell that lists them. CPU only."""

import json
import sys

import pytest

from benchmark_proc import CELLS, ROOT, last_line, run

BENCH_DIR = ROOT / "benchmark"
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

from harness import spec  # noqa: E402

LM = ["digest_trinity_backlog", "digest_keye_longform", "digest_xing_chapters"]
HOST = "host: the worker process"
MINE = {"lm_host_idle_pct": ("%", "program_span",
                             "engine: lm/engine.py + lm/cache.py", LM),
        "host_gc_ms_per_s": ("ms/s", "program_counter", HOST, CELLS[:5]),
        "host_pause_max_ms": ("ms", "program_span", HOST, CELLS[:5])}
# the accepted benchmark's 45 entries, in their order (as the accepted
# checks of the Keye and Xing cells list them)
ACCEPTED = [
    "asr_occupancy.backlog", "asr_tick_ms.backlog", "asr_mfu_pct",
    "asr_program_roofline", "device_idle_pct.backlog",
    "asr_tick_gap_ms.backlog", "asr_device_wait_ms.backlog",
    "asr_language_pass_ms.backlog", "asr_engine_build_s",
    "lm_mfu_pct.digest", "lm_moe_roofline.digest", "lm_attn_roofline.digest",
    "lm_step_ms.digest", "lm_step_gap_ms.digest", "lm_decode_rows.digest",
    "lm_prefill_tokens.digest", "lm_expert_load.digest",
    "lm_window_pages_pct.digest", "device_idle_pct.digest",
    "lm_index_roofline.longform", "lm_select_roofline.longform",
    "lm_sparse_attn_roofline.longform", "lm_selected_keys_pct.longform",
    "lm_pool_wait_rows.longform", "lm_mfu_pct.longform",
    "lm_moe_roofline.longform", "lm_step_ms.longform",
    "lm_step_gap_ms.longform", "lm_decode_rows.longform",
    "lm_prefill_tokens.longform", "lm_expert_load.longform",
    "device_idle_pct.longform",
    "lm_latent_rows_roofline.chapters",
    "lm_latent_chunk_roofline.chapters", "lm_hc_roofline.chapters",
    "lm_moe_roofline.chapters", "lm_mfu_pct.chapters",
    "lm_step_ms.chapters", "lm_step_gap_ms.chapters",
    "lm_decode_rows.chapters", "lm_prefill_tokens.chapters",
    "lm_expert_load.chapters", "lm_pool_wait_rows.chapters",
    "lm_rows_context.chapters", "device_idle_pct.chapters"]
TWO = ACCEPTED[:2]          # the list-free metrics every cell reports
# what each cell reported before this PR, in its order: the Whisper
# cells their nine, each transcript cell the two and its own
BEFORE = {"asr_small_backlog": ACCEPTED[:9],
          "asr_medium_backlog": ACCEPTED[:9],
          "digest_trinity_backlog": TWO + ACCEPTED[9:19],
          "digest_keye_longform": TWO + ACCEPTED[19:32],
          "digest_xing_chapters": TWO + ACCEPTED[32:]}


def _read(name, ctx):
    f = json.loads((BENCH_DIR / "layer_metrics" / f"{name}.json").read_text())
    return spec.plugin("readers", f["reader"]).read(ctx, **f.get("args", {}))


def test_the_three_entries_follow_what_was_there_and_keep_to_the_contract():
    bench = spec.load_bench()
    assert spec.check_names(bench) == []
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:45] == ACCEPTED
    assert len(names) >= 48 and names[45:48] == list(MINE)
    assert [w["name"] for w in bench["workloads"]][:5] == list(BEFORE)
    assert len(bench["configs"]) >= 5
    layers = {m["layer"] for m in bench["per_layer"][:45]}
    for m in bench["per_layer"][45:48]:
        unit, source, layer, cells = MINE[m["name"]]
        assert (m["unit"], m["source"], m["layer"], m["workloads"]) == (
            unit, source, layer, cells)
        assert m["better"] == "lower" and m["moves"] == "audio_s_per_s"
        assert layer == HOST or layer in layers      # letter for letter
        f = json.loads((BENCH_DIR / "layer_metrics" / f"{m['name']}.json"
                        ).read_text())
        assert {k: f[k] for k in ("name", "unit", "better", "source",
                                  "layer", "moves")} == {
            k: m[k] for k in ("name", "unit", "better", "source", "layer",
                              "moves")}
        assert callable(spec.plugin("readers", f["reader"]).read)
    # an end-to-end metric the three move, reported in every cell listed
    e2e = next(m for m in bench["end_to_end"]
               if m["name"] == "audio_s_per_s")
    assert set(CELLS[:5]) <= set(e2e["workloads"])


@pytest.mark.parametrize("cell", list(BEFORE))
def test_every_cell_reports_what_it_reported_and_the_new_ones(cell):
    """What the accepted checks of each cell's metrics asserted, but for
    their counts: the cell's old entries exactly, in their order, then
    this PR's that list the cell; its two end-to-end metrics; every
    entry lists the cell if it lists cells and has a reader; on a
    transcript cell every entry moves ``audio_s_per_s`` and the cell's
    own old entries list it alone."""
    loaded = spec.load_cell(cell)
    names = [m["name"] for m in loaded.per_layer]
    old = BEFORE[cell]
    assert names[:len(old)] == old
    assert names[len(old):] == [n for n, (*_x, cells) in MINE.items()
                                if cell in cells]
    assert [m["name"] for m in loaded.end_to_end] == ["audio_s_per_s",
                                                      "setup_s"]
    for m in loaded.per_layer:
        assert cell in (m.get("workloads") or [cell])
        if cell in LM:
            assert m["moves"] == "audio_s_per_s"
            if m["name"] in old[2:]:
                assert m["workloads"] == [cell]
        assert callable(spec.plugin("readers", m["reader"]).read)


def test_readers_say_nothing_where_the_program_records_nothing():
    # the parent's records: every other key, none of this PR's
    old_step = {"t_start": 0.0, "t_dispatch": 0.01, "t_ready": 0.02,
                "t_end": 0.03, "step_s": 0.01, "gap_s": 0.001,
                "phase_s": {"device_wait": 0.01}}
    old_tick = {"t_start": 0.0, "t_end": 1.3, "gap_s": 0.06, "n": 8,
                "rows": 8, "phase_s": {"device_wait": 1.2}}
    for name in MINE:
        empty = {"trace": None, "peaks": None, "batch_log": []}
        assert _read(name, empty) is None
        assert _read(name, {**empty, "step_log": [old_step],
                            "window": {"t0": 0.0, "t_end": 1.0}}) is None
        assert _read(name, {**empty, "batch_log": [old_tick]}) is None
        traced = {"trace": {"window_s": 6.0, "busy_s": 5.9},
                  "trace_steps": [old_step], "step_log": [old_step],
                  "batch_log": [old_tick]}
        assert _read(name, traced) is None


def _step(t, idle, gc_s=0.0, gap=0.0005):
    return {"t_start": t, "t_dispatch": t + 0.004, "t_ready": t + 0.02,
            "t_end": t + 0.03, "idle_before_s": idle, "gc_s": gc_s,
            "wait": {"polls": 40, "gap_max_s": gap, "cpu_s": 0.001,
                     "gc_s": 0.0, "wait_s": 0.01}}


def test_host_idle_reads_the_traced_steps_over_the_captures_stretch():
    steps = [_step(0.02 * i, idle) for i, idle in
             enumerate([None, 0.0, 0.001, 0.0, 0.003])]
    ctx = {"trace": {"window_s": 0.2, "busy_s": 0.19},
           "trace_steps": steps[1:], "step_log": steps,
           "window": {"t0": 0.0, "t_end": 10.0}}
    # 4 ms of idle the host caused over a 200 ms stretch
    assert _read("lm_host_idle_pct", ctx) == pytest.approx(2.0)
    # no capture (a rehearsal): the window's steps over the window
    assert _read("lm_host_idle_pct", {**ctx, "trace": None}) == \
        pytest.approx(100.0 * 0.004 / 10.0)
    # a capture in which no step of the window ended
    assert _read("lm_host_idle_pct", {**ctx, "trace_steps": []}) is None


def test_gc_per_second_prefers_step_records_and_falls_back_to_ticks():
    steps = [_step(0.0, 0.0, gc_s=0.003), _step(0.03, 0.0, gc_s=0.0)]
    ticks = [{"t_start": 0.0, "t_end": 2.0, "gc_s": 0.05,
              "wait": {"gap_max_s": 0.004}},
             {"t_start": 2.0, "t_end": 4.0, "gc_s": 0.01,
              "wait": {"gap_max_s": 0.25}},
             {"t_start": 4.0, "t_end": 5.0, "gc_s": None, "wait": None}]
    # 3 ms of collections over 60 ms of the records' stretches
    assert _read("host_gc_ms_per_s", {"step_log": steps,
                                      "batch_log": ticks}) == \
        pytest.approx(50.0)
    # the LM drivers' stand-in tick records carry no gc_s: steps win
    assert _read("host_gc_ms_per_s", {"step_log": steps, "batch_log": [
        {"n": 3, "rows": 4, "elapsed_s": 0.02}]}) == pytest.approx(50.0)
    assert _read("host_gc_ms_per_s", {"batch_log": ticks}) == \
        pytest.approx(1000.0 * 0.06 / 4.0)
    assert _read("host_pause_max_ms", {"batch_log": ticks}) == \
        pytest.approx(250.0)
    steps[1]["wait"]["gap_max_s"] = 0.0021
    assert _read("host_pause_max_ms", {"step_log": steps,
                                       "batch_log": ticks}) == \
        pytest.approx(2.1)


def _lists_mine(line, cell):
    assert line["correct"] is True and line["failed"] == 0
    mine = {n for n, (*_x, cells) in MINE.items() if cell in cells}
    assert mine <= set(line["metrics"])
    for name in mine:
        assert line["metrics"][name] == {"value": None,
                                         "unit": MINE[name][0]}
    waits = line["extra"]["engine_stats"]["waits"]
    assert waits["count"] >= 1 and set(waits["stalls"]) == {
        "gc", "host", "runtime"}
    assert 1 <= len(waits["longest"]) <= 5
    assert set(waits["process_gc"]) == {"seconds", "collections"}
    return mine


def test_a_whisper_cells_traced_rehearsal_lists_them(tmp_path):
    """Through ``records.py``, as ``test_benchmark_program_records.py``'s
    fixture runs it; what that file's traced-line test asserts besides
    its closed set of metrics (which this PR's two widen) holds too."""
    out = tmp_path / "rec.json"
    line = last_line(run(["benchmark/records.py", "--out", str(out),
                          "--workload", CELLS[0], "--seed",
                          str(2**31 + 37), "--seconds", "4", "--trace", "1",
                          "--rehearse"]))
    kept = json.loads(out.read_text())
    mine = _lists_mine(line, CELLS[0])
    assert mine == {"host_gc_ms_per_s", "host_pause_max_ms"}
    pr26 = {"asr_tick_gap_ms.backlog", "asr_device_wait_ms.backlog",
            "asr_language_pass_ms.backlog", "asr_engine_build_s"}
    assert {"asr_device_wait_ms.backlog", "asr_engine_build_s"} <= set(
        line["metrics"]) <= pr26 | mine | {"asr_occupancy.backlog",
                                           "asr_tick_ms.backlog"}
    assert ("asr_tick_gap_ms.backlog" in line["metrics"]) == any(
        t["gap_s"] is not None for t in kept["tick_records"])
    assert kept["correct"] is True and kept["workload"] == CELLS[0]
    assert len(kept["tick_records"]) == line["extra"]["ticks"] >= 1
    for t in kept["tick_records"]:
        assert t["wait"]["polls"] >= 2 and t["gc_s"] >= 0.0


def test_an_lm_cells_traced_rehearsal_lists_them():
    """Xing's cell; what ``test_benchmark_xing.py``'s rehearsal asserts
    besides its closed set of metrics (which this PR's three widen)
    holds too."""
    cell = "digest_xing_chapters"
    line = last_line(run(["benchmark/run.py", "--workload", cell, "--seed",
                          str(2**31 + 37), "--seconds", "3", "--trace", "1",
                          "--rehearse"]))
    mine = _lists_mine(line, cell)
    assert mine == set(MINE)
    own = [m["name"] for m in spec.load_cell(cell).per_layer]
    assert set(line["metrics"]) <= set(own)
    assert {"lm_rows_context.chapters", "lm_pool_wait_rows.chapters",
            "lm_decode_rows.chapters", "lm_expert_load.chapters",
            "asr_occupancy.backlog"} <= set(line["metrics"])
    extra = line["extra"]
    forms = extra["attn_forms"]
    assert set(forms) == {"latent_absorbed", "latent_expanded_loop"}
    assert forms["latent_absorbed"] == extra["steps"]
    stats = extra["engine_stats"]
    assert stats["attn_rows_form"] == "latent_absorbed"
    assert stats["pool"]["window"]["capacity"] == 0
    assert stats["pool"]["full"]["capacity"] == 96
    assert set(stats["pool_wait"]) == {"steps", "rows"}
    assert 0.0 < stats["hc_defect_max"] < 1e-4
    assert extra["pool"]["pages_in_use_max"] <= 96
    assert extra["rows_context_mean"] > 0
    assert line["compared"]["hc_defect"]["limit"] == 1e-4
