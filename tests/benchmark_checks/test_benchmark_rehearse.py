"""Each cell end to end at tiny widths on the CPU (``--rehearse``): the
last line parses, holds the contract's keys, names the CPU and carries
no device number."""

import pytest

from benchmark_proc import BENCH, CELLS, CONTRACT_KEYS, last_line, run


def _check_line(line, cell, traced):
    keys = list(line)
    assert keys[:5] == CONTRACT_KEYS
    assert keys[-1] == "compared"            # each number beside its limit
    assert set(keys) == set(CONTRACT_KEYS) | {"workload", "seed", "extra",
                                              "compared"}
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    group = "per_layer" if traced else "end_to_end"
    allowed = {m["name"] for m in BENCH[group]
               if "workloads" not in m or cell in m["workloads"]}
    assert line["metrics"] and set(line["metrics"]) <= allowed
    for m in line["metrics"].values():
        assert m["value"] is None      # a CPU run carries no device number
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell,traced", [(c, t) for c in CELLS
                                         for t in (0, 1)])
def test_rehearsal_of_every_cell(cell, traced):
    proc = run(["benchmark/run.py", "--workload", cell, "--seed",
                 str(2**31 + 17 + traced), "--seconds", "4", "--trace",
                 str(traced), "--rehearse"])
    line = last_line(proc)
    _check_line(line, cell, traced)
    tail = proc.stderr.strip().splitlines()
    assert tail[-1] == "correct: True"
    assert any(ln.startswith("compared beam_rank_gap: value")
               for ln in tail[-12:])
    if not traced:
        assert set(line["metrics"]) == {"audio_s_per_s", "setup_s"}


