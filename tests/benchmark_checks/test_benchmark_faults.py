"""The timed path broken underneath comes out not correct, and the
control (bfloat16 in the reference's place) fails its limit, at a size a
test run can hold."""

import json

import pytest

from benchmark_proc import ROOT, last_line, run


@pytest.mark.parametrize("fault,cell,number", [
    ("token", "asr_small_backlog", "beam_rank_gap"),
    ("cue", "asr_medium_backlog", "cue_mismatches"),
])
def test_a_broken_timed_path_comes_out_not_correct(fault, cell, number):
    proc = run(["benchmark/faults.py", "--fault", fault, "--workload", cell,
                 "--seed", "23", "--seconds", "5", "--trace", "0"])
    line = last_line(proc)
    assert line["correct"] is False
    c = line["compared"][number]
    assert c["value"] > c["limit"]
    assert proc.stderr.strip().splitlines()[-1] == "correct: False"


def test_the_control_fails_the_limit_at_test_size():
    """bfloat16 in the reference's place reads over the rehearsal limit
    of ``nospeech_logp_err`` and the program reads far under it."""
    proc = run(["benchmark/control.py", "--config", "whisper_small",
                 "--seeds", "3", "4", "5", "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    cfg = json.loads((ROOT / "benchmark/configs/whisper_small.json"
                      ).read_text())
    limit = cfg["rehearsal"]["check"]["nospeech_logp_err"]
    rows = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    assert len(rows) == 3
    for r in rows:
        assert r["program"]["nospeech_logp_err"] < limit / 10
        assert r["program"]["beam_rank_gap"] <= \
            cfg["rehearsal"]["check"]["beam_rank_gap"]
        assert r["control_bf16"]["nospeech_logp_err"] > limit
        assert r["program_quant_bf16"]["nospeech_logp_err"] > limit
        assert r["fault_token_swapped"]["beam_rank_gap_min"] > \
            cfg["rehearsal"]["check"]["beam_rank_gap"]
