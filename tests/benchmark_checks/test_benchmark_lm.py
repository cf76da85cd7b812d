"""The transcript cell's own arithmetic and its broken-path check: the
cut's parameter count, the step's operation and byte counts against a
hand count, a playlist that is the same for every seed, a credit that
sums to the audio, and a fault that turns ``correct`` false. CPU."""

import json
import sys

import pytest

from benchmark_proc import BENCH, ROOT, last_line, run

BENCH_DIR = ROOT / "benchmark"
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

from generators import transcript_backlog as gen  # noqa: E402
from harness import spec  # noqa: E402
from models import afmoe_costs as costs  # noqa: E402
from models.afmoe_weights import param_count  # noqa: E402

CELL = "digest_trinity_backlog"
CFG = json.loads((BENCH_DIR / "configs" / "trinity_mini_6l.json").read_text())
TRAFFIC = json.loads((BENCH_DIR / "traffic" / "transcripts_backlog.json"
                      ).read_text())


def test_the_cut_is_what_the_issue_reckoned():
    n = param_count(CFG)
    assert n["attention"] == 27_263_232
    assert n["dense_layer"] == 65_020_160
    assert n["expert_layer"] == 839_131_520
    assert n["embedding_and_head"] == 2 * 200_192 * 2048
    # 2 dense + 4 expert layers, embedding, head, final norm
    assert n["total"] == 2 * 65_020_160 + 4 * 839_131_520 \
        + 2 * 200_192 * 2048 + 2048 == 4_306_554_880
    whole = param_count(CFG, layers=CFG["published_num_hidden_layers"])
    assert 25.9e9 < whole["total"] < 26.3e9
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert len(CFG["layer_types"]) == CFG["published_num_hidden_layers"]
    kept = CFG["layer_types"][:CFG["num_hidden_layers"]]
    # one whole period after the two dense layers
    assert kept[2:].count("full_attention") == 1 and len(kept[2:]) == 4


def test_the_configuration_holds_the_catalog_rows_numbers():
    # the catalog lives beside the builder's guides, not in a checkout
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(path)]
    except OSError:
        pytest.skip(f"no catalog at {path}")
    row = next(r for r in rows if r["name"] == "Trinity-Mini")
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "trinity_mini_6l")
    assert entry["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in entry["reduced"]:
            assert CFG[k] != v
        else:
            assert CFG[k] == v, k
    assert CFG["published_num_hidden_layers"] == row["config"][
        "num_hidden_layers"]


def test_step_cost_against_a_hand_count():
    # 100 prefill tokens from position 3000 (the request's last chunk)
    # beside two rows at positions 10 and 5000
    c = costs.step_cost(CFG, prefill=100, context=3000, row_pos=[10, 5000],
                        last_chunk=True, experts_held=[128, 128, 128, 128])
    tokens = 102
    proj = 2 * 2048 * (4096 + 512 + 512 + 4096) + 2 * 4096 * 2048
    dense = 2 * 3 * 2048 * 6144
    expert = 2 * 3 * 2048 * 1024
    linear = tokens * (6 * proj + 2 * dense
                       + 4 * (2 * 2048 * 128 + expert))
    routed = 4 * tokens * 8 * expert
    full_keys = sum(range(3001, 3101)) + 11 + 5001
    win_keys = 100 * 2048 + 11 + 2048
    attn = 4 * 32 * 128 * (full_keys + 5 * win_keys)
    head = 3 * 2 * 2048 * 200_192
    assert c["parts"]["linear_flops"] == linear
    assert c["parts"]["experts"]["flops"] == routed
    assert c["parts"]["attn"]["flops"] == attn
    assert c["flops"] == linear + routed + attn + head
    # bytes: all experts' weights, each pair's row in and result out
    pairs = tokens * 8
    assert c["parts"]["experts"]["bytes"] == 4 * (
        128 * 3 * 2048 * 1024 * 2 + pairs * 2048 * 2 + pairs * 2048 * 4)
    # K and V of every visible key once, q in and heads out, per layer
    full_distinct = 3100 + 11 + 5001
    win_distinct = (3099 - (3000 - 2047) + 1) + 11 + 2048
    assert c["parts"]["attn"]["bytes"] == (
        2 * (full_distinct + 5 * win_distinct) * 4 * 128 * 2
        + 6 * 2 * tokens * 32 * 128 * 2)
    # a decode-only step of 32 rows reads weights, not operations
    d = costs.step_cost(CFG, prefill=0, context=0, row_pos=[4000] * 32,
                        last_chunk=False)
    least, bound = costs.least_seconds(d, {"flops_per_s": 197e12,
                                           "hbm_bytes_per_s": 819e9})
    assert bound == "bytes" and d["bytes"] > 7e9 and least > 0.008


def test_the_playlist_is_the_same_for_every_seed():
    plans = [gen.generate(TRAFFIC["params"], seed=s, seconds=51.0)
             for s in range(6)]
    assert all(p == plans[0] for p in plans[1:])
    playlist = plans[0]["playlist"]
    assert len(playlist) == 96 and plans[0]["clients"] == 32
    kinds = [p["kind"] for p in playlist]
    assert (kinds.count("clip"), kinds.count("talk"),
            kinds.count("stream")) == (58, 29, 9)
    prompts = [p["prompt_tokens"] for p in playlist]
    assert 512 + 200 <= min(prompts) and max(prompts) <= 512 + 36_000
    longer = sum(p for p in prompts if p > 2048)
    assert longer / sum(prompts) > 0.85     # most prompt tokens pass the window
    for p in playlist:
        assert p["prompt_tokens"] == 512 + round(p["audio_s"] * 10 / 3)
        assert p["output_tokens"] == {"clip": 96, "talk": 256,
                                      "stream": 384}[p["kind"]]
    # the longest request fits the deployment's context cap and pools
    dep = CFG["deployment"]
    assert max(prompts) + 384 <= dep["context_cap"]
    assert dep["full_pages"] - 1 >= dep["rows"] * -(
        -(max(prompts) + 384) // dep["page"])
    assert dep["window_pages"] - 1 == dep["rows"] * (
        CFG["sliding_window"] + dep["chunk"]) // dep["page"]


def test_equal_windows_of_steps_hold_equal_work():
    a = gen.generate(TRAFFIC["params"], seed=0, seconds=51.0)
    b = gen.generate(TRAFFIC["params"], seed=2**31 + 5, seconds=51.0)
    sa = gen.simulate(a, steps=1500, rows=32, chunk=2048)
    sb = gen.simulate(b, steps=1500, rows=32, chunk=2048)
    for lo, hi in ((0, 1500), (400, 900), (777, 1234)):
        for key in ("prefill_tokens", "decode_rows", "audio_s"):
            assert sum(s[key] for s in sa[lo:hi]) \
                == sum(s[key] for s in sb[lo:hi])
    # steady state: nearly every row decodes, most steps carry a chunk
    tail = sa[500:]
    assert sum(s["decode_rows"] for s in tail) / len(tail) > 24
    assert sum(1 for s in tail if s["prefill_tokens"]) / len(tail) > 0.4


def test_the_credit_of_a_playlist_cycle_sums_to_its_audio():
    plan = gen.generate(TRAFFIC["params"], seed=1, seconds=51.0)
    total = sum(gen.credit_per_token(p)
                * (p["prompt_tokens"] + p["output_tokens"])
                for p in plan["playlist"])
    assert total == pytest.approx(sum(p["audio_s"]
                                      for p in plan["playlist"]), rel=1e-12)
    # and through the schedule: every request that finished was credited
    # its whole audio
    steps = gen.simulate(plan, steps=4000, rows=32, chunk=2048)
    credited = sum(s["audio_s"] for s in steps)
    emitted: dict[int, int] = {}
    for s in steps:
        for i in s["emitted"]:
            emitted[i] = emitted.get(i, 0) + 1
    n = len(plan["playlist"])
    done = [i for i, k in emitted.items()
            if k == plan["playlist"][i % n]["output_tokens"]]
    assert len(done) > n
    assert credited >= sum(plan["playlist"][i % n]["audio_s"] for i in done)


def test_the_cell_and_its_metrics_keep_to_the_contract():
    bench = spec.load_bench()
    assert spec.check_names(bench) == []
    cell = spec.load_cell(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["audio_s_per_s",
                                                    "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    mine = [n for n in names if n.endswith(".digest")]
    # the two accepted metrics without a list of cells are every cell's
    # that reports audio_s_per_s (a step is this engine's tick); the
    # other three accepted ones read Whisper's program and list its cells
    assert len(mine) == 10 and sorted(set(names) - set(mine)) == [
        "asr_occupancy.backlog", "asr_tick_ms.backlog"]
    for m in cell.per_layer:
        assert m["moves"] == "audio_s_per_s"
        assert m.get("workloads") in (None, [CELL])
        assert callable(spec.plugin("readers", m["reader"]).read)
    # the Whisper cells report what they reported before
    for other in ("asr_small_backlog", "asr_medium_backlog"):
        mine = [m["name"] for m in spec.load_cell(other).per_layer]
        assert len(mine) == 9 and not any(n.startswith("lm_")
                                          or n.endswith(".digest")
                                          for n in mine)


PR26 = {"asr_tick_gap_ms.backlog": "program_span",
        "asr_device_wait_ms.backlog": "program_span",
        "asr_language_pass_ms.backlog": "program_span",
        "asr_engine_build_s": "program_counter"}


def test_new_entries_stand_after_all_that_were_there():
    """The order the driver holds a PR to: what the accepted benchmark
    had stays a prefix of every list, in its order."""
    names = [m["name"] for m in spec.load_bench()["per_layer"]]
    assert names[:5] == ["asr_occupancy.backlog", "asr_tick_ms.backlog",
                         "asr_mfu_pct", "asr_program_roofline",
                         "device_idle_pct.backlog"]
    assert names[5:9] == list(PR26)
    assert len(names[9:]) == 10 and all(n.endswith(".digest")
                                        for n in names[9:])
    assert [w["name"] for w in BENCH["workloads"]][-1] == CELL
    assert [c["name"] for c in BENCH["configs"]][-1] == "trinity_mini_6l"


@pytest.mark.parametrize("name,source", list(PR26.items()))
def test_an_entry_of_the_program_records_keeps_to_the_contract(name, source):
    """What ``test_the_four_entries_keep_to_the_contract`` asserts beside
    its pin of the last four places (see ``conftest.py``)."""
    m = next(m for m in spec.load_bench()["per_layer"] if m["name"] == name)
    assert m["source"] == source and m["better"] == "lower"
    assert m["workloads"] == ["asr_small_backlog", "asr_medium_backlog"]
    layers = {json.loads(f.read_text())["layer"]
              for f in (BENCH_DIR / "layer_metrics").glob("*.json")
              if f.stem not in PR26}
    assert m["layer"] in layers             # letter for letter
    f = json.loads((BENCH_DIR / "layer_metrics" / f"{name}.json"
                    ).read_text())
    assert callable(spec.plugin("readers", f["reader"]).read)


def test_readers_say_nothing_where_the_program_records_nothing():
    for m in spec.load_cell(CELL).per_layer:
        ctx = {"trace": None, "peaks": None, "batch_log": []}
        assert spec.plugin("readers", m["reader"]).read(
            ctx, **m["args"]) is None


def test_an_expert_weight_lost_under_the_timed_path_is_not_correct():
    proc = run(["benchmark/faults_lm.py", "--fault", "expert", "--workload",
                CELL, "--seed", "23", "--seconds", "3", "--trace", "0"])
    line = last_line(proc)
    assert line["correct"] is False
    c = line["compared"]["logit_err"]
    assert c["value"] > c["limit"]
    assert proc.stderr.strip().splitlines()[-1] == "correct: False"


def test_the_window_mask_control_fails_at_test_size():
    """Every control goes through the cell's own verdict: the program
    comes out correct, the reference without its window mask in the
    program's place does not (by the logit error), and the two lower
    precisions are read the same way (the rehearsal's limits are wide
    and its router margins large: whether THEY fail is the chip's to
    say, PERF.md section 6)."""
    proc = run(["benchmark/control_lm.py", "--config", "trinity_mini_6l",
                "--seeds", "5", "--seconds", "3", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(row["tokens"]) == {"clip", "talk", "stream"}
    assert row["program"]["correct"] is True
    names = set(row["program"]["compared"])
    mask = row["control_no_window_mask"]
    assert mask["correct"] is False
    err = mask["compared"]["logit_err"]
    assert err["value"] > err["limit"] > \
        row["program"]["compared"]["logit_err"]["value"]
    for name in ("control_bf16_compute", "control_int8_weights"):
        assert set(row[name]["compared"]) == names
        assert row[name]["compared"]["logit_err"]["value"] > \
            row["program"]["compared"]["logit_err"]["value"]
