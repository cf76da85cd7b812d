"""The long-recording cell's own arithmetic: the cut's parameter count,
the step's operation and byte counts against a hand count, the playlist
ISSUE 33 drew, the catalog row, the order of ``BENCHMARK.json``'s lists
(what ``test_benchmark_lm.py`` pinned to their ends, see
``tests/conftest.py``), readers that say nothing where nothing is
recorded, and controls that turn ``correct`` false. CPU."""

import json
import sys

import pytest

from benchmark_proc import BENCH, ROOT, run

BENCH_DIR = ROOT / "benchmark"
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

from generators import transcript_backlog as gen  # noqa: E402
from harness import spec  # noqa: E402
from models import keye_costs as costs  # noqa: E402
from models.keye_weights import param_count  # noqa: E402

CELL = "digest_keye_longform"
CONFIG = "keye_vl2_lm_6l"
CFG = json.loads((BENCH_DIR / "configs" / f"{CONFIG}.json").read_text())
TRAFFIC = json.loads((BENCH_DIR / "traffic" / "longform_backlog.json"
                      ).read_text())


def test_the_cut_is_what_the_issue_reckoned():
    n = param_count(CFG)
    assert n["experts"] == 603_979_776
    assert n["attention"] == 18_874_624         # with the two head norms
    assert n["indexer"] == 2048 * 1024 + 2048 * 64 + 2048 * 16 + 128 \
        == 2_261_120
    assert n["router"] == 262_144 and n["norms"] == 4_096
    assert n["layer"] == 625_381_760
    assert n["embedding_and_head"] == 622_329_856
    assert n["total"] == 6 * 625_381_760 + 622_329_856 + 2048 \
        == 4_374_622_464
    # the file states the same bytes: 8.75 GB in bfloat16
    assert "4,374,622,464" in CFG["cut"]
    assert round(2 * n["total"] / 1e9, 2) == 8.75
    whole = param_count(CFG, layers=CFG["published_num_hidden_layers"])
    assert round(2 * whole["total"] / 1e9, 1) == 61.3
    assert round(2 * param_count(CFG, layers=7)["total"] / 1e9, 1) == 10.0
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["published_num_hidden_layers"] == 48


def test_the_configuration_holds_the_catalog_rows_numbers():
    # the catalog lives beside the builder's guides, not in a checkout
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(path)]
    except OSError:
        pytest.skip(f"no catalog at {path}")
    row = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == CFG["source"]
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers"]
    for k, v in row["config"].items():      # nested groups whole
        if k in entry["reduced"]:
            assert CFG[k] != v
        else:
            assert CFG[k] == v, k
    assert {"sa_config", "rope_scaling"} <= set(row["config"])
    assert CFG["published_num_hidden_layers"] == row["config"][
        "num_hidden_layers"]


def test_step_cost_against_a_hand_count():
    # 100 prefill tokens from position 3000 (the request's last chunk)
    # beside rows at positions 10 (every key kept) and 5000
    c = costs.step_cost(CFG, prefill=100, context=3000, row_pos=[10, 5000],
                        last_chunk=True, experts_held=[128] * 6)
    tokens = 102
    proj = 2 * 2048 * (4096 + 512 + 512) + 2 * 4096 * 2048 \
        + 2 * 2048 * (16 * 64 + 64 + 16)
    expert = 2 * 3 * 2048 * 768
    linear = tokens * 6 * (proj + 2 * 2048 * 128)
    causal = sum(range(3001, 3101)) + 11 + 5001
    chosen = 100 * 2048 + 11 + 2048
    over = causal - 11              # the row at 10 chooses nothing
    parts = c["parts"]
    assert parts["linear_flops"] == linear
    assert parts["experts"]["flops"] == 6 * tokens * 8 * expert
    assert parts["index"]["flops"] == 6 * causal * (2 * 16 * 64 + 2 * 16)
    assert parts["select"]["flops"] == 6 * over
    assert parts["select"]["bytes"] == 6 * over * 4
    assert parts["sparse"]["flops"] == 6 * chosen * 4 * 32 * 128
    assert c["keys"] == {"causal": causal, "chosen": chosen}
    head = 3 * 2 * 2048 * 151_936
    assert c["flops"] == linear + sum(
        parts[p]["flops"] for p in ("experts", "index", "select", "sparse")
    ) + head
    # bytes: every indexer key of each sequence once, the queries in
    assert parts["index"]["bytes"] == 6 * (
        (3100 + 11 + 5001) * 64 * 2 + tokens * 16 * (64 * 2 + 4))
    # K and V of the keys a sequence can have chosen, q in, heads out:
    # the chunk's 100 queries choose 204,800 of 3,100 keys (all of them)
    assert parts["sparse"]["bytes"] == 6 * (
        2 * (3100 + 11 + 2048) * 4 * 128 * 2 + 2 * tokens * 32 * 128 * 2)
    pairs = tokens * 8
    assert parts["experts"]["bytes"] == 6 * (
        128 * 3 * 2048 * 768 * 2 + pairs * 2048 * 2 + pairs * 2048 * 4)
    # under the selection's size nothing is chosen: the sparse part is
    # the dense one and the choice costs nothing
    d = costs.step_cost(CFG, prefill=2048, context=0, row_pos=[],
                        last_chunk=False)
    assert d["keys"]["causal"] == d["keys"]["chosen"] == 2048 * 2049 // 2
    assert d["parts"]["select"] == {"flops": 0.0, "bytes": 0.0}
    # a long chunk attends a fifteenth of what a dense layer would
    e = costs.step_cost(CFG, prefill=2048, context=30_720, row_pos=[],
                        last_chunk=False)
    assert e["keys"]["chosen"] == 2048 * 2048
    assert 15 < e["keys"]["causal"] / e["keys"]["chosen"] < 16
    # a decode-only step of 16 rows reads weights, not operations
    f = costs.step_cost(CFG, prefill=0, context=0, row_pos=[20_000] * 16,
                        last_chunk=False)
    least, bound = costs.least_seconds(f, {"flops_per_s": 197e12,
                                           "hbm_bytes_per_s": 819e9})
    assert bound == "bytes" and f["bytes"] > 7e9 and least > 0.008


def test_the_playlist_is_the_one_the_issue_drew():
    plans = [gen.generate(TRAFFIC["params"], seed=s, seconds=51.0)
             for s in (0, 5, 2**31 + 9)]
    assert all(p == plans[0] for p in plans[1:])
    playlist = plans[0]["playlist"]
    assert plans[0]["clients"] == 16 and plans[0]["open_when_finished"] == 16
    kinds = [p["kind"] for p in playlist]
    assert (kinds.count("clip"), kinds.count("talk"),
            kinds.count("stream")) == (8, 24, 16)
    assert kinds[:16].count("stream") == 7
    prompts = [p["prompt_tokens"] for p in playlist]
    assert (min(prompts), max(prompts), sum(prompts)) == (750, 35_804,
                                                          669_422)
    assert round(sum(p["audio_s"] for p in playlist)) == 193_454
    topk = CFG["sa_config"]["topk"]
    longer = sum(p for p in prompts if p > topk)
    assert round(longer / sum(prompts), 3) == 0.984
    for p in playlist:
        assert p["prompt_tokens"] == 512 + round(p["audio_s"] * 10 / 3)
        assert p["output_tokens"] == {"clip": 96, "talk": 256,
                                      "stream": 768}[p["kind"]]
        if p["kind"] == "clip":         # every key chosen
            assert p["prompt_tokens"] + 96 <= topk
    # the pool, not the rows, bounds what is resident
    dep = CFG["deployment"]
    pages = [-(-(p["prompt_tokens"] + p["output_tokens"]) // dep["page"])
             for p in playlist]
    assert max(prompts) + 768 <= dep["context_cap"]
    assert dep["window_pages"] == 0 and dep["full_pages"] - 1 >= 1000
    assert dep["rows"] * max(pages) > dep["full_pages"] - 1 \
        > 7 * max(pages)
    assert sum(pages[:16]) > 0.85 * (dep["full_pages"] - 1)
    mean = sum(p["prompt_tokens"] + p["output_tokens"]
               for p in playlist) / len(playlist)
    assert round(mean) == 14_346
    # 13,056 bytes a position over the six layers
    per_position = 6 * (2 * 4 * 128 + 64) * 2
    assert per_position == 13_056
    assert "13,056" in dep["pools_why"]


ACCEPTED = ["asr_occupancy.backlog", "asr_tick_ms.backlog", "asr_mfu_pct",
            "asr_program_roofline", "device_idle_pct.backlog",
            "asr_tick_gap_ms.backlog", "asr_device_wait_ms.backlog",
            "asr_language_pass_ms.backlog", "asr_engine_build_s",
            "lm_mfu_pct.digest", "lm_moe_roofline.digest",
            "lm_attn_roofline.digest", "lm_step_ms.digest",
            "lm_step_gap_ms.digest", "lm_decode_rows.digest",
            "lm_prefill_tokens.digest", "lm_expert_load.digest",
            "lm_window_pages_pct.digest", "device_idle_pct.digest"]
MINE = ["lm_index_roofline.longform", "lm_select_roofline.longform",
        "lm_sparse_attn_roofline.longform", "lm_selected_keys_pct.longform",
        "lm_pool_wait_rows.longform", "lm_mfu_pct.longform",
        "lm_moe_roofline.longform", "lm_step_ms.longform",
        "lm_step_gap_ms.longform", "lm_decode_rows.longform",
        "lm_prefill_tokens.longform", "lm_expert_load.longform",
        "device_idle_pct.longform"]


def test_what_was_there_is_a_prefix_of_every_list():
    """What of ``test_new_entries_stand_after_all_that_were_there``
    still holds: the accepted benchmark's entries come first in every
    list, in their order, and this PR's stand after them."""
    bench = spec.load_bench()
    assert spec.check_names(bench) == []
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:19] == ACCEPTED and names[19:] == MINE
    assert [w["name"] for w in bench["workloads"]] == [
        "asr_small_backlog", "asr_medium_backlog", "digest_trinity_backlog",
        CELL]
    assert [c["name"] for c in bench["configs"]] == [
        "whisper_small", "whisper_medium", "trinity_mini_6l", CONFIG]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert list(e2e) == ["audio_s_per_s", "setup_s"]
    assert e2e["audio_s_per_s"]["workloads"][-1] == CELL
    assert e2e["audio_s_per_s"]["bound"] == 0.03
    assert e2e["setup_s"]["bound"] == 0.1 and bench["run_seconds"] == 51
    layers = {m["layer"] for m in bench["per_layer"]}
    for m in bench["per_layer"][19:]:
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


@pytest.mark.parametrize("cell,count,own", [
    ("asr_small_backlog", 9, []), ("asr_medium_backlog", 9, []),
    ("digest_trinity_backlog", 12, ACCEPTED[9:]), (CELL, 15, MINE)])
def test_every_cell_reports_what_it_reported(cell, count, own):
    """Trinity's cell its ten and the two list-free metrics, the Whisper
    cells their nine, the new cell its thirteen and the same two."""
    names = [m["name"] for m in spec.load_cell(cell).per_layer]
    assert len(names) == count
    assert names[:2] == ["asr_occupancy.backlog", "asr_tick_ms.backlog"]
    if own:
        assert names[2:] == own
    else:
        assert names == ACCEPTED[:9]
    loaded = spec.load_cell(cell)
    assert [m["name"] for m in loaded.end_to_end] == ["audio_s_per_s",
                                                      "setup_s"]
    for m in loaded.per_layer:
        assert callable(spec.plugin("readers", m["reader"]).read)


def test_readers_say_nothing_where_the_program_records_nothing():
    # as on the parent commit, whose step records hold no such keys
    old = {"step_s": 0.1, "gap_s": 0.0, "decode_rows": 3,
           "prefill_tokens": 0, "expert_load": [[1, 8, 4]],
           "window_pages": [3, 9]}
    for m in spec.load_cell(CELL).per_layer:
        read = spec.plugin("readers", m["reader"]).read
        ctx = {"trace": None, "peaks": None, "batch_log": []}
        assert read(ctx, **m["args"]) is None
        if m["name"] in ("lm_selected_keys_pct.longform",
                         "lm_pool_wait_rows.longform"):
            assert read({**ctx, "step_log": [old]}, **m["args"]) is None
    read = spec.plugin("readers", "keye_selected_keys_pct").read
    assert read({"step_log": [{"sparse_keys": [10, 40]},
                              {"sparse_keys": [20, 80]}]}) == 25.0


def test_a_roofline_share_reads_least_seconds_over_scope_seconds():
    rec = {"prefill_tokens": 2048, "context": 20_480, "row_pos": [9000] * 4,
           "chunk_tag": "a", "emitted": ["b"], "expert_load": [[9, 9, 100]] * 6}
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    cost = costs.record_cost(CFG, rec)
    ctx = {"trace_steps": [rec, rec], "peaks": peaks, "model": CFG,
           "scope_s": {"lm.attn.index": 0.5, "lm.attn.sparse": 0.25}}
    read = spec.plugin("readers", "keye_scope_roofline").read
    least = costs.least_seconds(cost["parts"]["index"], peaks)[0]
    assert read(ctx, part="index", scopes=["lm.attn.index"]) \
        == pytest.approx(100 * 2 * least / 0.5)
    assert read(ctx, part="select", scopes=["lm.attn.select"]) is None
    mfu = spec.plugin("readers", "keye_mfu_pct").read
    assert mfu({**ctx, "trace": {"busy_s": 2.0}}) == pytest.approx(
        100 * 2 * cost["flops"] / 2.0 / 197e12)


def test_the_selection_controls_fail_at_test_size():
    """Every control goes through the cell's own verdict: the program
    comes out correct; the references that attend the newest keys, or
    every key, in place of the learned choice do not, by the long kinds'
    number, and leave the clip (which chooses nothing) where it was; the
    lower precision moves the clip's number, which is the chip's to hold
    to a limit (PERF.md section 6)."""
    proc = run(["benchmark/control_keye.py", "--config", CONFIG,
                "--seeds", "5", "--seconds", "3", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(row["tokens"]) == {"clip", "talk", "stream"}
    program = row["program"]
    assert program["correct"] is True
    names = list(program["compared"])
    assert names == [
        "positions_compared", "kinds_compared", "logit_err", "beam_rank_gap",
        "route_tie_share", "flipped_share", "long_kinds_compared",
        "long_logit_err", "select_tie_share",
        "requests_failed_or_never_finished", "seconds_building_in_window"]
    assert program["compared"]["long_kinds_compared"]["value"] == 2
    mine = program["compared"]["long_logit_err"]["value"]
    for name in ("control_newest_keys", "control_dense_attention"):
        other = row[name]
        assert other["correct"] is False
        err = other["compared"]["long_logit_err"]
        assert err["value"] > err["limit"] > mine
        assert other["compared"]["logit_err"] \
            == program["compared"]["logit_err"]
        assert other["errs"]["clip"] == program["errs"]["clip"]
    low = row["control_bf16_compute"]
    assert list(low["compared"]) == names
    assert low["compared"]["logit_err"]["value"] \
        > program["compared"]["logit_err"]["value"]
    # one key chosen otherwise in every layer moves a talk's logits, and
    # float32 operands lie off the stated precision: both are readings
    assert len(row["one_key_swapped"]) == len(row["float32_operands"]) == 3
    assert row["one_key_swapped"][2] > 0 and row["float32_operands"][2] > 0
