"""The chapters cell's own arithmetic: the cut's parameter count, the
step's operation and byte counts against a hand count, the playlist
ISSUE 35 drew, the catalog row, the order of ``BENCHMARK.json``'s lists
(what ``test_benchmark_keye.py`` pinned to their ends, see
``tests/conftest.py``; nothing here pins this PR's entries to the end),
readers that say nothing where nothing is recorded, controls that turn
``correct`` false, and the cell's rehearsal. CPU."""

import json
import sys

import pytest

from benchmark_proc import BENCH, ROOT, last_line, run

BENCH_DIR = ROOT / "benchmark"
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

from generators import transcript_backlog as gen  # noqa: E402
from harness import spec  # noqa: E402
from models import xing_costs as costs  # noqa: E402
from models.xing_weights import param_count  # noqa: E402

CELL = "digest_xing_chapters"
CONFIG = "xing4_29b_6l"
CFG = json.loads((BENCH_DIR / "configs" / f"{CONFIG}.json").read_text())
TRAFFIC = json.loads((BENCH_DIR / "traffic" / "chapters_backlog.json"
                      ).read_text())


def test_the_cut_is_what_the_issue_reckoned():
    n = param_count(CFG)
    assert n["attention"] == 3584 * 768 + 768 + 768 * 32 * 192 \
        + 3584 * 576 + 512 + 512 * 32 * 256 + 4096 * 3584 == 28_411_136
    assert n["hyper_connections"] == 2 * (14_336 * 24 + 24 + 3) == 688_182
    assert n["dense_layer"] == 128_196_918
    assert n["routed_experts"] == 64 * 11_010_048
    assert n["shared_expert"] == 11_010_048 and n["router"] == 229_440
    assert n["expert_layer"] == 744_989_046
    assert n["embedding_and_head"] == 939_524_096
    assert n["total"] == 2 * 128_196_918 + 4 * 744_989_046 + 939_524_096 \
        + 3584 == 4_175_877_700
    # the file states the same bytes: 8.35 GB in bfloat16
    assert "4,175,877,700" in CFG["cut"] and "28,411,136" in CFG["cut"]
    assert round(2 * n["total"] / 1e9, 2) == 8.35
    whole = param_count(CFG, layers=CFG["published_num_hidden_layers"])
    assert round(whole["total"] / 1e9, 1) == 29.5
    assert round(2 * whole["total"] / 1e9, 1) == 59.0
    assert round(2 * param_count(CFG, layers=7)["total"] / 1e9, 2) == 9.84
    # active: everything but 60 of the 64 routed experts of 38 layers
    active = whole["total"] - 38 * 60 * 11_010_048
    assert round(active / 1e9, 2) == 4.40
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["published_num_hidden_layers"] == 40
    assert CFG["num_hidden_layers"] == 6 and CFG["first_k_dense_replace"] == 2


def test_the_configuration_holds_the_catalog_rows_numbers():
    # the catalog lives beside the builder's guides, not in a checkout
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(path)]
    except OSError:
        pytest.skip(f"no catalog at {path}")
    row = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == CFG["source"]
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    for k, v in row["config"].items():      # nested groups whole
        if k in entry["reduced"]:
            assert CFG[k] != v
        else:
            assert CFG[k] == v, k
    assert "rope_scaling" in row["config"]
    assert CFG["published_num_hidden_layers"] == row["config"][
        "num_hidden_layers"]
    # the widths, by name: none cut
    assert (CFG["hidden_size"], CFG["num_attention_heads"]) == (3584, 32)
    assert (CFG["q_lora_rank"], CFG["kv_lora_rank"], CFG["qk_nope_head_dim"],
            CFG["qk_rope_head_dim"], CFG["v_head_dim"]) \
        == (768, 512, 128, 64, 128)
    assert (CFG["n_routed_experts"], CFG["num_experts_per_tok"],
            CFG["moe_intermediate_size"], CFG["intermediate_size"],
            CFG["vocab_size"]) == (64, 4, 1024, 9216, 131_072)
    assert (CFG["hc_mult"], CFG["hc_sinkhorn_iters"]) == (4, 20)
    assert "left out" in CFG["mtp"] and CFG["num_nextn_predict_layers"] == 1
    reh = CFG["rehearsal"]
    assert (reh["hidden_size"], reh["num_attention_heads"],
            reh["q_lora_rank"], reh["kv_lora_rank"], reh["qk_nope_head_dim"],
            reh["qk_rope_head_dim"], reh["n_routed_experts"],
            reh["num_experts_per_tok"], reh["hc_mult"],
            reh["num_hidden_layers"], reh["first_k_dense_replace"]) \
        == (64, 4, 24, 16, 16, 8, 8, 2, 4, 4, 1)


def test_step_cost_against_a_hand_count():
    # 100 prefill tokens from position 3000 (the request's last chunk)
    # beside rows at positions 10 and 5000
    c = costs.step_cost(CFG, prefill=100, context=3000, row_pos=[10, 5000],
                        last_chunk=True, experts_held=[64] * 4)
    tokens = 102
    proj = 2 * 3584 * 768 + 2 * 768 * 32 * 192 + 2 * 3584 * 576 \
        + 2 * 4096 * 3584
    dense = 2 * 3 * 3584 * 9216
    expert = 2 * 3 * 3584 * 1024
    linear = tokens * (6 * proj + 2 * dense
                       + 4 * (2 * 3584 * 64 + expert))
    parts = c["parts"]
    assert parts["linear_flops"] == linear
    assert parts["experts"]["flops"] == 4 * tokens * 4 * expert
    # the rows absorbed: 32 heads over ONE key of 576 whose first 512
    # lanes are the value; absorption and up-projection once a row
    keys = 11 + 5001
    assert parts["rows"]["flops"] == 6 * (
        keys * 2 * 32 * (576 + 512) + 2 * 2 * 32 * 512 * (128 + 128))
    assert parts["rows"]["bytes"] == 6 * (
        keys * 576 * 2 + 512 * 8192 * 2 + 2 * 32 * (576 * 2 + 512 * 4))
    # the chunk expanded: ONE expansion of its 3,100 positions a layer
    pairs = sum(range(3001, 3101))
    assert parts["chunk"]["flops"] == 6 * (
        pairs * 2 * 32 * 320 + 3100 * 2 * 512 * 8192)
    assert parts["chunk"]["bytes"] == 6 * (
        3100 * 576 * 2 + 512 * 8192 * 2 + 100 * 32 * (192 * 2 + 128 * 4))
    assert c["keys"] == {"rows": keys, "chunk_pairs": pairs,
                         "chunk_context": 3100}
    # the residual path: 129,024 B a token a sublayer and the projection
    assert parts["hc"]["bytes"] == 6 * 2 * (
        tokens * (2 * 4 * 3584 * 4 + 3584 * 4) + 14_336 * 24 * 2)
    assert 2 * 4 * 3584 * 4 + 3584 * 4 == 129_024
    assert parts["hc"]["flops"] == 6 * tokens * 2 * (
        2 * 14_336 * 24 + 2 * 4 * 3584 + 2 * 20 * 3584)
    head = 3 * 2 * 3584 * 131_072
    assert c["flops"] == linear + sum(
        parts[p]["flops"] for p in ("experts", "rows", "chunk", "hc")) + head
    routed = tokens * 4
    assert parts["experts"]["bytes"] == 4 * (
        64 * 3 * 3584 * 1024 * 2 + routed * 3584 * 2 + routed * 3584 * 4)
    # the arithmetic that decides the forms (ISSUE 35): a 2,048-token
    # chunk at context L costs L x 50.3 MFLOP a layer expanded
    # (L x 142.6 absorbed); one query costs 0.07 MFLOP a key absorbed
    # (8.4 expanded)
    a = costs.step_cost(CFG, prefill=2048, context=34_000, row_pos=[],
                        last_chunk=False)["parts"]["chunk"]["flops"] / 6
    # (the issue's L is the context at the chunk's end; causal, the
    # chunk's own keys count half)
    assert 0.95 < a / (50.3e6 * 36_048) < 1.0
    assert 2 * 2048 * 32 * 1088 / 1e6 == pytest.approx(142.6, abs=0.05)
    assert 2 * 32 * 1088 / 1e6 == pytest.approx(0.07, abs=0.001)
    assert 2 * 512 * 8192 / 1e6 == pytest.approx(8.4, abs=0.02)
    # a decode-only step of 32 rows over 635k positions reads 4.4 GB of
    # latents and some 6.5 GB of weights (128 choices fall on 55 of a
    # layer's 64 experts): bytes, not operations
    f = costs.step_cost(CFG, prefill=0, context=0, row_pos=[19_843] * 32,
                        last_chunk=False, experts_held=[55] * 4)
    assert 4.3e9 < f["parts"]["rows"]["bytes"] < 4.5e9
    assert 6.0e9 < f["parts"]["weight_bytes"] \
        + f["parts"]["experts"]["bytes"] < 6.7e9
    least, bound = costs.least_seconds(f, {"flops_per_s": 197e12,
                                           "hbm_bytes_per_s": 819e9})
    assert bound == "bytes" and 0.012 < least < 0.015


def test_the_playlist_is_the_one_the_issue_drew():
    plans = [gen.generate(TRAFFIC["params"], seed=s, seconds=51.0)
             for s in (0, 5, 2**31 + 9)]
    assert all(p == plans[0] for p in plans[1:])
    playlist = plans[0]["playlist"]
    assert len(playlist) == 64
    assert plans[0]["clients"] == 32 and plans[0]["open_when_finished"] == 32
    kinds = [p["kind"] for p in playlist]
    assert (kinds.count("clip"), kinds.count("talk"),
            kinds.count("stream")) == (8, 32, 24)
    prompts = [p["prompt_tokens"] for p in playlist]
    assert (min(prompts), max(prompts), sum(prompts)) == (717, 36_247,
                                                          975_921)
    assert sum(p["output_tokens"] for p in playlist) == 41_984
    assert round(sum(p["audio_s"] for p in playlist), 1) == 282_946.5
    long_ = sum(p for p in prompts if p > 16_000)
    assert round(long_ / sum(prompts), 3) == 0.667
    for p in playlist:
        assert p["prompt_tokens"] == 512 + round(p["audio_s"] * 10 / 3)
        assert p["output_tokens"] == {"clip": 128, "talk": 512,
                                      "stream": 1024}[p["kind"]]
    dep = CFG["deployment"]
    assert max(p["prompt_tokens"] + p["output_tokens"] for p in playlist) \
        == 37_271 <= dep["context_cap"]
    # exactly the parameters of ISSUE 35's Tentpole 4
    assert TRAFFIC["generator"] == "transcript_backlog"
    assert TRAFFIC["params"]["schedule_seed"] == 35
    assert TRAFFIC["params"]["instruction_tokens"] == 512
    assert TRAFFIC["params"]["tokens_per_audio_s"] == [10, 3]
    assert [(c["name"], c["count"], c["audio_s"], c["output_tokens"])
            for c in TRAFFIC["params"]["classes"]] == [
        ("clip", 8, [60, 400], 128), ("talk", 32, [1800, 3600], 512),
        ("stream", 24, [5400, 10800], 1024)]
    assert (TRAFFIC["trace_after_s"], TRAFFIC["trace_seconds"]) == (4.0, 6.0)
    # decode-bound: 31.5 of 32 rows decode, under half the steps a chunk
    tail = gen.simulate(plans[0], steps=6000, rows=32, chunk=2048)[2000:]
    assert sum(s["decode_rows"] for s in tail) / len(tail) > 31
    assert 0.35 < sum(1 for s in tail if s["prefill_tokens"]) / len(tail) \
        < 0.45
    # ONE pool on the full class's page numbers, 6,912 B a position
    assert dep["window_pages"] == 0 and 2561 <= dep["full_pages"] <= 2817
    assert dep["rows"] == 32 and dep["chunk"] == 2048 and dep["page"] == 256
    assert 6 * 576 * 2 == 6912 and "6,912" in dep["pools_why"]


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
    "device_idle_pct.longform"]
MINE = ["lm_latent_rows_roofline.chapters",
        "lm_latent_chunk_roofline.chapters", "lm_hc_roofline.chapters",
        "lm_moe_roofline.chapters", "lm_mfu_pct.chapters",
        "lm_step_ms.chapters", "lm_step_gap_ms.chapters",
        "lm_decode_rows.chapters", "lm_prefill_tokens.chapters",
        "lm_expert_load.chapters", "lm_pool_wait_rows.chapters",
        "lm_rows_context.chapters", "device_idle_pct.chapters"]
ACCEPTED_CELLS = ["asr_small_backlog", "asr_medium_backlog",
                  "digest_trinity_backlog", "digest_keye_longform"]
ACCEPTED_CONFIGS = ["whisper_small", "whisper_medium", "trinity_mini_6l",
                    "keye_vl2_lm_6l"]


def _in_order(names, wanted):
    """``wanted`` appear in ``names`` in their order, one after another
    (nothing of another kind between them is asked)."""
    at = [names.index(n) for n in wanted]
    return at == list(range(at[0], at[0] + len(wanted)))


def test_what_was_there_is_a_prefix_and_this_prs_entries_follow_it():
    """What of ``test_benchmark_keye.py``'s order still holds: the
    accepted benchmark's 32 entries, four cells and four configurations
    come first in their lists, in their order; this PR's thirteen follow
    them, in order. What a later PR appends after these is not this
    test's to forbid."""
    bench = spec.load_bench()
    assert spec.check_names(bench) == []
    names = [m["name"] for m in bench["per_layer"]]
    assert len(ACCEPTED) == 32 and names[:32] == ACCEPTED
    assert names[32:45] == MINE and _in_order(names, MINE)
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:4] == ACCEPTED_CELLS and cells[4] == CELL
    configs = [c["name"] for c in bench["configs"]]
    assert configs[:4] == ACCEPTED_CONFIGS and configs[4] == CONFIG
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert list(e2e) == ["audio_s_per_s", "setup_s"]
    assert e2e["audio_s_per_s"]["workloads"][:5] == ACCEPTED_CELLS + [CELL]
    assert e2e["audio_s_per_s"]["bound"] == 0.03
    assert e2e["setup_s"]["bound"] == 0.1 and bench["run_seconds"] == 51
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "chapters_backlog"
    layers = {m["layer"] for m in bench["per_layer"][:32]} | {
        "kernels: lm/model.py latent attention",
        "kernels: lm/model.py hyper-connections"}
    for m in bench["per_layer"][32:45]:
        assert m["workloads"] == [CELL] and m["moves"] == "audio_s_per_s"
        f = json.loads((BENCH_DIR / "layer_metrics" / f"{m['name']}.json"
                        ).read_text())
        assert {k: f[k] for k in ("unit", "better", "source", "layer",
                                  "moves")} == {k: m[k] for k in (
                                      "unit", "better", "source", "layer",
                                      "moves")}
        assert m["layer"] in layers
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["source"] == "device_trace"
    rows = next(m for m in bench["per_layer"]
                if m["name"] == "lm_rows_context.chapters")
    assert (rows["unit"], rows["better"], rows["source"]) \
        == ("1", "higher", "program_counter")


@pytest.mark.parametrize("cell,count,own", [
    ("asr_small_backlog", 9, []), ("asr_medium_backlog", 9, []),
    ("digest_trinity_backlog", 12, ACCEPTED[9:19]),
    ("digest_keye_longform", 15, ACCEPTED[19:]), (CELL, 15, MINE)])
def test_every_cell_reports_what_it_reported(cell, count, own):
    """The Whisper cells their nine, Trinity's its ten and Keye's its
    thirteen beside the two list-free metrics, the new cell its thirteen
    and the same two: fifteen."""
    loaded = spec.load_cell(cell)
    names = [m["name"] for m in loaded.per_layer]
    assert len(names) == count
    assert names[:2] == ["asr_occupancy.backlog", "asr_tick_ms.backlog"]
    if own:
        assert names[2:] == own
    else:
        assert names == ACCEPTED[:9]
    assert [m["name"] for m in loaded.end_to_end] == ["audio_s_per_s",
                                                      "setup_s"]
    for m in loaded.per_layer:
        assert callable(spec.plugin("readers", m["reader"]).read)


def test_readers_say_nothing_where_the_program_records_nothing():
    # as on the parent commit, whose step records hold no such keys
    old = {"step_s": 0.1, "gap_s": 0.0, "decode_rows": 3,
           "prefill_tokens": 0, "expert_load": [[1, 8, 4]],
           "window_pages": [3, 9], "pool_wait_rows": 0}
    for m in spec.load_cell(CELL).per_layer:
        read = spec.plugin("readers", m["reader"]).read
        ctx = {"trace": None, "peaks": None, "batch_log": []}
        assert read(ctx, **m["args"]) is None
        if m["name"] == "lm_rows_context.chapters":
            assert read({**ctx, "step_log": [old]}, **m["args"]) is None
            assert read({**ctx, "step_log": [{"rows_context": 10},
                                             {"rows_context": 30}]},
                        **m["args"]) == 20.0
        if "roofline" in m["name"]:
            # a capture of a program without the scopes
            assert read({**ctx, "trace_steps": [old], "model": CFG,
                         "peaks": {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
                         "scope_s": {"lm.attn.full": 1.0}},
                        **m["args"]) is None


def test_a_roofline_share_reads_least_seconds_over_scope_seconds():
    rec = {"prefill_tokens": 2048, "context": 20_480,
           "row_pos": [9000] * 31, "chunk_tag": "a", "emitted": ["b"],
           "expert_load": [[9, 9, 64]] * 4}
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    cost = costs.record_cost(CFG, rec)
    ctx = {"trace_steps": [rec, rec], "peaks": peaks, "model": CFG,
           "scope_s": {"lm.attn.latent.rows": 0.5,
                       "lm.attn.latent.expand": 0.1,
                       "lm.attn.latent.chunk": 0.15, "lm.hc.map": 0.01}}
    read = spec.plugin("readers", "xing_scope_roofline").read
    for part, scopes, took in (
            ("rows", ["lm.attn.latent.rows"], 0.5),
            ("chunk", ["lm.attn.latent.expand", "lm.attn.latent.chunk"], 0.25),
            ("hc", ["lm.hc.map", "lm.hc.pre", "lm.hc.post"], 0.01)):
        least = costs.least_seconds(cost["parts"][part], peaks)[0]
        assert read(ctx, part=part, scopes=scopes) \
            == pytest.approx(100 * 2 * least / took)
    assert costs.least_seconds(cost["parts"]["rows"], peaks)[1] == "bytes"
    assert costs.least_seconds(cost["parts"]["chunk"], peaks)[1] == "flops"
    assert costs.least_seconds(cost["parts"]["hc"], peaks)[1] == "bytes"
    assert read(ctx, part="experts", scopes=["lm.moe.experts"]) is None
    mfu = spec.plugin("readers", "xing_mfu_pct").read
    assert mfu({**ctx, "trace": {"busy_s": 2.0}}) == pytest.approx(
        100 * 2 * cost["flops"] / 2.0 / 197e12)


def test_the_controls_fail_at_rehearsal_size():
    """Every control goes through the cell's own verdict: the program
    comes out correct; the reference without the rope key does not, by
    the logits; the reference with one Sinkhorn iteration and the
    reference wholly in bfloat16 do not, by their mappings' distance
    from doubly stochastic (``hc_defect``: the sum of the streams, which
    is all the head reads, does not change with ``H_res`` while its
    columns sum to 1); the identity in ``H_res``'s place moves the
    logits of the kinds it replaced by three times the program's own
    error and more, which at hidden 64 lies inside bfloat16's noise and
    is the chip's to hold to a limit (PERF.md section 6)."""
    proc = run(["benchmark/control_xing.py", "--config", CONFIG,
                "--seeds", "7", "--seconds", "3", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(row["tokens"]) == {"clip", "talk", "stream"}
    program = row["program"]
    assert program["correct"] is True
    names = list(program["compared"])
    assert names == [
        "positions_compared", "kinds_compared", "logit_err", "beam_rank_gap",
        "route_tie_share", "flipped_share", "median_logit_err", "hc_defect",
        "requests_failed_or_never_finished", "seconds_building_in_window"]
    assert program["compared"]["hc_defect"]["value"] < 1e-5
    norope = row["control_no_rope_key"]
    err = norope["compared"]["logit_err"]
    assert norope["correct"] is False and err["value"] > err["limit"]
    for name in ("control_one_sinkhorn", "control_bf16_compute"):
        other = row[name]
        assert list(other["compared"]) == names
        defect = other["compared"]["hc_defect"]
        assert other["correct"] is False
        assert defect["value"] > defect["limit"] == 1e-4
    ident = row["control_identity_h_res"]
    assert ident["compared"]["hc_defect"]["value"] == 0.0
    for kind in ("clip", "talk"):
        assert ident["errs"][kind][0] > 2 * program["errs"][kind][0]
    # the stream was left to the program in every control
    for name in row:
        if name.startswith("control_"):
            assert row[name]["errs"]["stream"] == program["errs"]["stream"]


def test_the_cells_rehearsal_names_its_forms_and_its_pool():
    line = last_line(run(["benchmark/run.py", "--workload", CELL, "--seed",
                          str(2**31 + 35), "--seconds", "3", "--trace", "1",
                          "--rehearse"]))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) <= set(MINE) | {"asr_occupancy.backlog",
                                                "asr_tick_ms.backlog"}
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
