"""The benchmark's own arithmetic (benchmark/, BENCHMARK.json): CPU,
seconds each. Nothing here times anything."""

import json
import math
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
HERE = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from generators import burst_clips, closed_loop  # noqa: E402
from harness import spec, stats, trace  # noqa: E402
from models import whisper_costs, whisper_weights  # noqa: E402


# ---- percentiles and failures ---------------------------------------------

@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (90, 4.6),
                                    (100, 5.0)])
def test_percentile_interpolates_between_ranks(q, want):
    assert stats.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == \
        pytest.approx(want)


def test_failed_requests_miss_every_latency():
    ok = [1.0] * 9
    assert stats.latency_percentile(ok, 0, 90) == 1.0
    # one failure in ten: the median holds, the 95th percentile is a miss
    assert stats.latency_percentile(ok, 1, 50) == 1.0
    assert stats.latency_percentile(ok, 1, 95) is None
    assert stats.latency_percentile([], 3, 50) is None
    assert stats.latency_percentile([], 0, 50) is None


def test_spread_is_iqr_over_median_by_statistics_quantiles():
    vals = [10.0, 10.2, 9.9, 10.1, 10.0, 10.3]
    import statistics

    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))


# ---- generators -------------------------------------------------------------

CLIPS = json.loads((BENCH / "traffic" / "clips.json").read_text())
BACKLOG = json.loads((BENCH / "traffic" / "backlog.json").read_text())


def test_clip_schedule_same_seed_same_schedule_other_seed_other_order():
    a = burst_clips.generate(CLIPS["params"], seed=2**31 + 9, seconds=51.0)
    b = burst_clips.generate(CLIPS["params"], seed=2**31 + 9, seconds=51.0)
    c = burst_clips.generate(CLIPS["params"], seed=3, seconds=51.0)
    assert a == b
    # every seed offers the same bursts at the same instants, so the same
    # load, with the clips of a burst in another order
    assert [j["audio_s"] for j in a["jobs"]] != \
        [j["audio_s"] for j in c["jobs"]]
    shape = [(j["due_s"], j["burst"], burst_clips.windows_of(j["audio_s"]))
             for j in a["jobs"]]
    assert shape == [(j["due_s"], j["burst"],
                      burst_clips.windows_of(j["audio_s"]))
                     for j in c["jobs"]]
    assert sorted((j["burst"], j["audio_s"]) for j in a["jobs"]) == \
        sorted((j["burst"], j["audio_s"]) for j in c["jobs"])
    assert a["offered_windows_per_s"] == c["offered_windows_per_s"]
    assert all(0.0 <= j["due_s"] < 51.0 for j in a["jobs"])


def test_clip_schedule_carries_the_offered_load_and_the_burst_shapes():
    p = CLIPS["params"]
    base = burst_clips.base_schedule(p)
    windows = sum(burst_clips.windows_of(j["audio_s"]) for j in base)
    assert windows == pytest.approx(p["offered_windows_per_s"]
                                    * p["cycle_s"], abs=2)
    sizes = {}
    for j in base:
        sizes[j["burst"]] = sizes.get(j["burst"], 0) + 1
        assert p["clip_s"][0] <= j["audio_s"] <= p["clip_s"][1]
    assert 1 <= min(sizes.values()) and max(sizes.values()) <= p["burst_max"]
    assert burst_clips.windows_of(30.0) == 1
    assert burst_clips.windows_of(30.1) == 2
    assert burst_clips.windows_of(55.0) == 2


def test_closed_loop_gives_every_seed_the_same_sizes_in_another_order():
    params = {"clients": 4, "recording_s": 600.0,
              "first_recording_s": [155.0, 305.0, 455.0, 600.0]}
    plans = [closed_loop.generate(params, seed=s, seconds=51.0)
             for s in range(8)]
    firsts = [tuple(c["first_s"] for c in p["clients"]) for p in plans]
    assert all(sorted(f) == params["first_recording_s"] for f in firsts)
    assert len(set(firsts)) > 1
    assert plans[0] == closed_loop.generate(params, seed=0, seconds=51.0)
    shipped = closed_loop.generate(BACKLOG["params"], seed=1, seconds=51.0)
    assert len(shipped["clients"]) == BACKLOG["params"]["clients"]
    assert all(c["then_s"] == 600.0 for c in shipped["clients"])


# ---- operations and bytes ---------------------------------------------------

def test_tick_cost_agrees_with_a_hand_count_at_a_tiny_size():
    cfg = dict(d_model=4, encoder_layers=1, decoder_layers=1,
               encoder_attention_heads=1, decoder_attention_heads=1,
               encoder_ffn_dim=8, decoder_ffn_dim=8, vocab_size=10,
               num_mel_bins=2, max_source_positions=3,
               max_target_positions=6)
    got = whisper_costs.tick_cost(cfg, windows=1, beams=2, steps=1,
                                  prompt_len=1)
    d, t, ffn, v, mels = 4, 3, 8, 10, 2
    mel = 3000 * 5 * 400 * math.log2(400) + 2 * 3000 * 201 * mels
    conv = 2 * 3 * mels * d * 3000 + 2 * 3 * d * d * t
    enc = conv + 8 * t * d * d + 4 * t * t * d + 4 * t * d * ffn
    ckv = 4 * t * d * d

    def step(rows, ctx):
        layer = 8 * d * d + 4 * ctx * d + 4 * d * d + 4 * t * d + 4 * d * ffn
        return rows * (layer + 2 * d * v + 5 * v)

    dec = step(1, 1) + step(2, 2)          # one prompt step, one generated
    assert got["parts"]["mel_flops"] == pytest.approx(mel)
    assert got["parts"]["encoder_flops"] == pytest.approx(enc)
    assert got["parts"]["cross_kv_flops"] == pytest.approx(ckv)
    assert got["parts"]["decoder_flops"] == pytest.approx(dec)
    assert got["flops"] == pytest.approx(mel + enc + ckv + dec)

    attn = 4 * d * d + 3 * d + 2 * d
    enc_w = (3 * mels * d + d + 3 * d * d + d + t * d
             + (attn + 2 * d * ffn + ffn + d + 2 * d) + 2 * d)
    dec_w = (2 * attn + 2 * d * ffn + ffn + d + 2 * d) + 2 * d + v * d + 6 * d
    ckv_b = 2 * t * d * 4
    tick_b = enc_w * 4 + (mels * 3000 + t * d) * 4 + ckv_b
    step_b = lambda rows, ctx: (dec_w * 4 + ckv_b  # noqa: E731
                                + rows * 2 * (ctx + 1) * d * 4 + rows * v * 4)
    assert got["bytes"] == pytest.approx(tick_b + step_b(1, 0) + step_b(2, 1))


def test_roofline_takes_the_larger_bound_and_names_it():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert whisper_costs.least_seconds({"flops": 1000.0, "bytes": 50.0},
                                       peaks) == (10.0, "flops")
    assert whisper_costs.least_seconds({"flops": 100.0, "bytes": 50.0},
                                       peaks) == (5.0, "bytes")


def test_published_widths_give_the_published_parameter_counts():
    small = json.loads((BENCH / "configs" / "whisper_small.json").read_text())
    medium = json.loads((BENCH / "configs" / "whisper_medium.json"
                         ).read_text())
    # openai/whisper: small 244M, medium 769M (with the fixed position
    # table counted); ours draws every leaf
    assert whisper_weights.param_count(small) == 241_734_912
    assert whisper_weights.param_count(medium) == 763_857_920


# ---- the trace reader on a small recorded trace -----------------------------

def test_trace_reduction_on_the_recorded_trace():
    planes = json.loads((HERE / "recorded_trace.json").read_text())
    for p in planes:
        for ln in p["lines"]:
            ln["events"] = [tuple(e) for e in ln["events"]]
    got = trace.reduce_planes(planes)
    want = json.loads((HERE / "recorded_trace.expect.json").read_text())
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["modules"] == want["modules"]
    assert [n for n, _ in got["device_ops"]] == \
        [n for n, _ in want["device_ops"]]
    assert [n for n, _ in got["idle_gaps"]] == \
        [n for n, _ in want["idle_gaps"]]


def test_trace_reduction_counts_leaves_once_and_labels_gaps():
    us = 1000
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                (0, 400 * us, "jit_step(123)"),         # cut by the start
                (2600 * us, 3000 * us, "jit_step(123)"),
                (4000 * us, 4400 * us, "jit_step(123)")]},
            {"name": "XLA Ops", "events": [
                (0, 400 * us, "%while.1 = (s32[]) while(...)"),
                (0, 100 * us, "%fusion.1 = f32[8] fusion(...)"),
                (100 * us, 400 * us, "%fusion.2 = f32[8] fusion(...)"),
                (2600 * us, 3000 * us, "%fusion.2 = f32[8] fusion(...)"),
                (4000 * us, 4400 * us, "%fusion.2 = f32[8] fusion(...)")]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                (380 * us, 2620 * us, "bench:parse"),
                (0, 6000 * us, "bench:job")]}]},
    ]
    got = trace.reduce_planes(planes)
    assert got["window_s"] == pytest.approx(6e-3)
    assert got["busy_s"] == pytest.approx(1.2e-3)      # the while is not added
    assert got["modules"] == {"jit_step": {"runs": 2,
                                           "seconds": pytest.approx(0.8e-3)}}
    assert got["device_ops"][0] == ["fusion.2", pytest.approx(1.1e-3)]
    assert got["idle_gaps"] == [["parse", pytest.approx(2.2e-3)],
                                ["job", pytest.approx(2.6e-3)]][::-1]


# ---- names, units, files ----------------------------------------------------

def test_benchmark_json_keeps_to_the_contracts_names_and_limits():
    bench = spec.load_bench()
    assert spec.check_names(bench) == []
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_name_in_benchmark_json_finds_its_files():
    bench = spec.load_bench()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["reduced"] == next(
            c["reduced"] for c in bench["configs"]
            if c["name"] == w["config"])
        spec.plugin("generators", cell.traffic["generator"])
        assert (BENCH / "drivers" / f"{cell.config['driver']}.py").exists()
        assert cell.per_layer, f"{w['name']} reports no per-layer metric"
        assert len(cell.end_to_end) >= 2
        for m in cell.per_layer:
            assert callable(spec.plugin("readers", m["reader"]).read)
    for m in bench["per_layer"]:
        f = json.loads((BENCH / "layer_metrics" / f"{m['name']}.json"
                        ).read_text())
        for k in ("layer", "unit", "moves", "source", "better"):
            assert f[k] == m[k], (m["name"], k)
    for c in bench["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
    for p in BENCH.rglob("*"):
        if ".cache" in p.parts or "__pycache__" in p.parts:
            continue
        assert all(ch.isalnum() or ch in "_.-" for ch in p.name), p


def test_peaks_table_knows_the_chip_and_refuses_a_stranger():
    assert spec.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert spec.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks_for("TPU v9 imaginary")
