"""The talks cell's own arithmetic: the cut's parameter count term by
term, the published numbers, the step's operation and byte counts
against a hand count, the playlist the traffic file draws, the order of
``BENCHMARK.json``'s lists (what was there stays a prefix; the cell's
entries follow it),
readers that say nothing where nothing is recorded, controls that turn
``correct`` false, and the cell's rehearsal. CPU."""

import json
import math
import sys

import pytest

from benchmark_proc import BENCH, ROOT, last_line, run

BENCH_DIR = ROOT / "benchmark"
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

from generators import transcript_backlog as gen  # noqa: E402
from harness import spec  # noqa: E402
from models import qwen3next_costs as costs  # noqa: E402
from models.qwen3next_weights import param_count  # noqa: E402

CELL = "digest_qwen3next_talks"
CONFIG = "qwen3_next_80b_4l"
CFG = json.loads((BENCH_DIR / "configs" / f"{CONFIG}.json").read_text())
TRAFFIC = json.loads((BENCH_DIR / "traffic" / "talks_backlog.json"
                      ).read_text())
MINE = ["lm_gdn_chunk_roofline.talks", "lm_gdn_rows_roofline.talks",
        "lm_gated_attn_roofline.talks", "lm_held_choices_pct.talks",
        "lm_mfu_pct.talks", "lm_moe_roofline.talks", "lm_step_ms.talks",
        "lm_step_gap_ms.talks", "lm_decode_rows.talks",
        "lm_prefill_tokens.talks", "lm_expert_load.talks",
        "lm_pool_wait_rows.talks", "device_idle_pct.talks"]
BEFORE_CELLS = ["asr_small_backlog", "asr_medium_backlog",
                "digest_trinity_backlog", "digest_keye_longform",
                "digest_xing_chapters"]
BEFORE_CONFIGS = ["whisper_small", "whisper_medium", "trinity_mini_6l",
                  "keye_vl2_lm_6l", "xing4_29b_6l"]
BEFORE_METRICS = 48


def test_the_cut_counts_term_by_term():
    n = param_count(CFG)
    assert n["deltanet"] == 25_165_824 + 131_072 + 32_768 + 64 + 128 \
        + 8_388_608 == 33_718_464
    assert n["attention"] == 16_777_216 + 2 * 1_048_576 + 8_388_608 + 512 \
        == 27_263_488
    assert n["outside_experts"] == 1_048_576 + 3_145_728 + 2048 + 4096 \
        == 4_200_448
    assert n["routed_expert"] == 3 * 2048 * 512 == 3_145_728
    assert n["embedding_and_head"] == 2 * 151_936 * 2048 == 622_329_856
    assert n["deltanet_layer"] == 33_718_464 + 4_200_448 + 256 * 3_145_728 \
        == 843_225_280
    assert n["attention_layer"] == 836_770_304
    assert n["total"] == 3 * 843_225_280 + 836_770_304 + 622_329_856 + 2048 \
        == 3_988_778_048
    assert round(2 * n["total"] / 1e9, 2) == 7.98
    assert "3,988,778,048" in CFG["cut"] and "843,225,280" in CFG["cut"]
    # four layers with every expert do not fit beside anything
    whole = param_count(CFG, experts=512)
    assert whole["total"] == 7_210_003_520
    assert round(2 * whole["total"] / 1e9, 1) == 14.4
    assert round(param_count(CFG, layers=48, experts=512)["total"] / 1e9,
                 1) == 79.7
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts"]
    assert (CFG["published_num_hidden_layers"], CFG["published_num_experts"],
            CFG["first_held_expert"]) == (48, 512, 0)
    assert CFG["deployment"]["layers_share_chips"] == 2
    # a request's slot: three layers of state and conv tail
    assert 3 * (32 * 128 * 128 * 4 + 3 * 8192 * 2) == 6_438_912
    assert "6,438,912" in CFG["deployment"]["pools_why"]


# the published config.json's keys that fix the model's shape
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
          "config.json")


def test_the_configuration_holds_the_published_numbers():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE == CFG["source"]
    assert entry["reduced"] == CFG["reduced"]
    for k, v in PUBLISHED.items():
        if k in entry["reduced"]:
            assert CFG[k] != v and CFG[f"published_{k}"] == v
        else:
            assert CFG[k] == v, k
    assert "left out" in CFG["mtp"] and CFG["num_nextn_predict_layers"] == 1
    reh = CFG["rehearsal"]
    assert (reh["num_hidden_layers"], reh["num_experts"],
            reh["published_num_experts"]) == (4, 8, 16)


def test_step_cost_against_a_hand_count():
    # 100 prefill tokens from position 3000 (the request's last chunk)
    # beside rows at positions 10 and 5000; 700 held pairs
    c = costs.step_cost(CFG, prefill=100, context=3000, row_pos=[10, 5000],
                        last_chunk=True, held_pairs=700,
                        experts_busy=[200] * 4)
    tokens, parts = 102, c["parts"]
    gdn_proj = 2 * 2048 * (12_288 + 64) + 2 * 4096 * 2048 + 2 * 4 * 8192
    attn_proj = 2 * 2048 * (8192 + 1024) + 2 * 4096 * 2048
    per_layer = 2 * 2048 * 512 + 2 * 3 * 2048 * 512 + 2 * 2048
    assert parts["linear_flops"] == tokens * (3 * gdn_proj + attn_proj
                                              + 4 * per_layer)
    # the chunkwise rule a value head and a token: C (3 dk + 2 dv) + C^2/3
    # + 6 dk dv, C = 64
    chunk_tok = 32 * (64 * 640 + 64 * 64 / 3 + 6 * 128 * 128)
    assert parts["gdn_chunk"]["flops"] == pytest.approx(3 * 100 * chunk_tok)
    state = 32 * 128 * 128 * 4
    io = (2 * 16 * 128 + 32 * 128 + 64 + 32 * 128) * 4
    assert parts["gdn_chunk"]["bytes"] == 3 * (2 * state + 100 * io)
    assert parts["gdn_rows"]["flops"] == 3 * 2 * 32 * 7 * 128 * 128
    assert parts["gdn_rows"]["bytes"] == 3 * 2 * (2 * state + io)
    keys = 11 + 5001
    pairs = sum(range(3001, 3101))
    assert parts["attn"]["flops"] == (keys + pairs) * 2 * 16 * 256 * 2 \
        + tokens * 16 * 256
    assert parts["attn"]["bytes"] == (keys + 3100) * 2048 \
        + tokens * 4096 * 10
    assert parts["experts"]["flops"] == 700 * 6 * 2048 * 512
    assert parts["experts"]["bytes"] == 800 * 3 * 2048 * 512 * 2 \
        + 700 * 2048 * 6
    head = 3 * 2 * 2048 * 151_936
    assert c["flops"] == pytest.approx(parts["linear_flops"] + sum(
        parts[p]["flops"] for p in ("gdn_chunk", "gdn_rows", "attn",
                                    "experts")) + head)
    # a decode step of 64 rows: the states, read and written, are 0.8 GB,
    # bytes and not operations
    f = costs.step_cost(CFG, prefill=0, context=0, row_pos=[9000] * 64,
                        last_chunk=False)
    assert 0.80e9 < f["parts"]["gdn_rows"]["bytes"] < 0.82e9
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert costs.least_seconds(f["parts"]["gdn_rows"], peaks)[1] == "bytes"
    # a whole chunk: 27.6 GFLOP through the three layers' chunkwise rule
    full = costs.step_cost(CFG, prefill=2048, context=8192, row_pos=[],
                           last_chunk=False)["parts"]["gdn_chunk"]
    assert 27.5e9 < full["flops"] < 27.7e9


def test_the_playlist_is_the_same_for_every_seed():
    plans = [gen.generate(TRAFFIC["params"], seed=s, seconds=51.0)
             for s in (0, 5, 2**31 + 9)]
    assert all(p == plans[0] for p in plans[1:])
    playlist = plans[0]["playlist"]
    assert len(playlist) == 128
    assert plans[0]["clients"] == 64 and plans[0]["open_when_finished"] == 64
    kinds = [p["kind"] for p in playlist]
    assert (kinds.count("clip"), kinds.count("talk"),
            kinds.count("stream")) == (32, 80, 16)
    prompts = [p["prompt_tokens"] for p in playlist]
    assert 712 <= min(prompts) and max(prompts) <= 36_512
    for p in playlist:
        assert p["prompt_tokens"] == 512 + round(p["audio_s"] * 10 / 3)
        assert p["output_tokens"] == {"clip": 128, "talk": 256,
                                      "stream": 512}[p["kind"]]
    assert TRAFFIC["params"]["schedule_seed"] == 39
    assert [(c["name"], c["count"], c["audio_s"], c["output_tokens"])
            for c in TRAFFIC["params"]["classes"]] == [
        ("clip", 32, [60, 600], 128), ("talk", 80, [1200, 3600], 256),
        ("stream", 16, [5400, 10800], 512)]
    dep = CFG["deployment"]
    assert max(p["prompt_tokens"] + p["output_tokens"] for p in playlist) \
        <= dep["context_cap"]
    # nearly every step a chunk beside 40 to 64 decoding rows
    tail = gen.simulate(plans[0], steps=6000, rows=64, chunk=2048)[1000:]
    assert sum(1 for s in tail if s["prefill_tokens"]) / len(tail) > 0.95
    rows = sum(s["decode_rows"] for s in tail) / len(tail)
    assert 40 < rows < 64
    # the pool holds the schedule's peak of reservations
    assert dep["window_pages"] == 0 and dep["full_pages"] - 1 >= 3112
    assert dep["rows"] == 64 and 2 * 2 * 256 * 2 == 2048
    assert math.ceil(36_751 / 256) <= dep["full_pages"]


def test_what_was_there_is_a_prefix_and_the_cells_entries_follow_it():
    bench = spec.load_bench()
    assert spec.check_names(bench) == []
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) >= BEFORE_METRICS + len(MINE)
    assert names[BEFORE_METRICS:BEFORE_METRICS + len(MINE)] == MINE
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:6] == BEFORE_CELLS + [CELL]
    configs = [c["name"] for c in bench["configs"]]
    assert configs[:6] == BEFORE_CONFIGS + [CONFIG]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["audio_s_per_s"]["workloads"][:6] == BEFORE_CELLS + [CELL]
    assert e2e["audio_s_per_s"]["bound"] == 0.03
    assert e2e["setup_s"]["bound"] == 0.1 and bench["run_seconds"] == 51
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "talks_backlog"
    assert len(cell["why"]) <= 200
    layers = {m["layer"] for m in bench["per_layer"][:BEFORE_METRICS]} | {
        "kernels: lm/model.py Gated DeltaNet"}
    for m in bench["per_layer"][BEFORE_METRICS:BEFORE_METRICS + len(MINE)]:
        assert m["workloads"] == [CELL] and m["moves"] == "audio_s_per_s"
        f = json.loads((BENCH_DIR / "layer_metrics" / f"{m['name']}.json"
                        ).read_text())
        assert {k: f[k] for k in ("name", "unit", "better", "source",
                                  "layer", "moves")} == {
            k: m[k] for k in ("name", "unit", "better", "source", "layer",
                              "moves")}
        assert m["layer"] in layers
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["source"] == "device_trace"
    loaded = spec.load_cell(CELL)
    assert [m["name"] for m in loaded.per_layer] == [
        "asr_occupancy.backlog", "asr_tick_ms.backlog"] + MINE
    assert [m["name"] for m in loaded.end_to_end] == ["audio_s_per_s",
                                                      "setup_s"]


def test_readers_say_nothing_where_the_program_records_nothing():
    # as on the parent commit, whose step records hold no such keys
    old = {"step_s": 0.1, "gap_s": 0.0, "decode_rows": 3,
           "prefill_tokens": 0, "expert_load": [[1, 8, 4]],
           "pool_wait_rows": 0, "row_pos": [5], "context": None,
           "chunk_tag": None, "emitted": []}
    for m in spec.load_cell(CELL).per_layer:
        read = spec.plugin("readers", m["reader"]).read
        ctx = {"trace": None, "peaks": None, "batch_log": []}
        assert read(ctx, **m["args"]) is None
        if m["name"] == "lm_held_choices_pct.talks":
            assert read({**ctx, "step_log": [old]}, **m["args"]) is None
            assert read({**ctx, "step_log": [
                {"held_choices": [30, 40]}, {"held_choices": [10, 40]}]},
                **m["args"]) == 50.0
        if "roofline" in m["name"]:
            # a capture of a program without the scopes
            assert read({**ctx, "trace_steps": [old], "model": CFG,
                         "peaks": {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
                         "scope_s": {"lm.attn.window": 1.0}},
                        **m["args"]) is None


def test_a_roofline_share_reads_least_seconds_over_scope_seconds():
    rec = {"prefill_tokens": 2048, "context": 8192,
           "row_pos": [9000] * 50, "chunk_tag": "a", "emitted": ["b"],
           "expert_load": [[30, 900, 250]] * 4, "held_choices": [3600, 8000]}
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    cost = costs.record_cost(CFG, rec)
    assert cost["parts"]["experts"]["flops"] == 3600 * 6 * 2048 * 512
    ctx = {"trace_steps": [rec, rec], "peaks": peaks, "model": CFG,
           "scope_s": {"lm.gdn.chunk": 0.02, "lm.gdn.rows": 0.01,
                       "lm.attn.full": 0.005, "lm.moe.experts": 0.03}}
    read = spec.plugin("readers", "qwen3next_scope_roofline").read
    for part, scope in (("gdn_chunk", "lm.gdn.chunk"),
                        ("gdn_rows", "lm.gdn.rows"),
                        ("attn", "lm.attn.full"),
                        ("experts", "lm.moe.experts")):
        least = costs.least_seconds(cost["parts"][part], peaks)[0]
        assert read(ctx, part=part, scopes=[scope]) == pytest.approx(
            100 * 2 * least / ctx["scope_s"][scope])
    mfu = spec.plugin("readers", "qwen3next_mfu_pct").read
    assert mfu({**ctx, "trace": {"busy_s": 2.0}}) == pytest.approx(
        100 * 2 * cost["flops"] / 2.0 / 197e12)


def test_the_controls_fail_at_rehearsal_size():
    """Every control goes through the cell's own verdict: the program
    comes out correct, and the reference with the state reset at every
    chunk, with no decay, with no conv tail or with the shared expert
    ungated does not, by its logits. The reference wholly in bfloat16
    is the chip's to hold to a limit: at hidden 64 the program's own
    bfloat16 products read as far from the float32 reference."""
    proc = run(["benchmark/control_qwen3next.py", "--config", CONFIG,
                "--seeds", "11", "--seconds", "3", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(row["tokens"]) == {"clip", "talk", "stream"}
    program = row["program"]
    assert program["correct"] is True
    names = list(program["compared"])
    assert names == [
        "positions_compared", "kinds_compared", "logit_err", "beam_rank_gap",
        "route_tie_share", "flipped_share", "median_logit_err",
        "requests_failed_or_never_finished", "seconds_building_in_window"]
    for name in ("control_state_reset", "control_no_decay",
                 "control_no_conv_tail", "control_ungated_shared"):
        other = row[name]
        assert other["correct"] is False, name
        err = other["compared"]["median_logit_err"]
        assert err["value"] > err["limit"], name
    # the stream was left to the program in every control
    for name in row:
        if name.startswith("control_"):
            assert row[name]["errs"]["stream"] == program["errs"]["stream"]


def test_the_cells_rehearsal_names_its_forms_and_its_slots():
    line = last_line(run(["benchmark/run.py", "--workload", CELL, "--seed",
                          str(2**31 + 39), "--seconds", "3", "--trace", "1",
                          "--rehearse"]))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) <= set(MINE) | {"asr_occupancy.backlog",
                                                "asr_tick_ms.backlog"}
    assert {"lm_held_choices_pct.talks", "lm_decode_rows.talks",
            "lm_expert_load.talks", "asr_occupancy.backlog"} \
        <= set(line["metrics"])
    extra = line["extra"]
    forms = extra["forms"]
    assert set(forms) == {"attn_rows.loop", "attn_chunk.loop",
                          "gdn_rows.recurrent", "gdn_chunk.chunkwise"}
    assert forms["gdn_rows.recurrent"] == extra["steps"]
    assert forms["gdn_chunk.chunkwise"] == forms["attn_chunk.loop"]
    stats = extra["engine_stats"]
    assert stats["state"]["slots"] == 4
    assert stats["pool"]["window"]["capacity"] == 0
    assert 1 <= extra["state_slots_max"] <= 4
    held, pairs = extra["held_choices"]
    assert 0 < held < pairs
