"""What ``run.py`` refuses, and a fourth cell added as data files only."""

import json
import shutil
from pathlib import Path

from benchmark_proc import CELLS, ROOT, last_line, run


def test_no_tpu_no_result():
    proc = run(["benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
                 "--seconds", "1", "--trace", "0"], timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_unknown_cell_is_refused():
    proc = run(["benchmark/run.py", "--workload", "nope", "--seed", "1",
                 "--seconds", "1", "--trace", "0", "--rehearse"], timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def _copy_benchmark(dst: Path) -> None:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", dst / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))


def test_benchmark_alone_is_refused(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no system
    under test, so no result."""
    _copy_benchmark(tmp_path)
    proc = run(["benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
                 "--seconds", "1", "--trace", "0", "--rehearse"],
                cwd=tmp_path, timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_a_new_cell_is_data_files_and_entries(tmp_path):
    """A third and a fourth cell of the existing driver, added without
    touching a file that was there: the open-loop clips cell (its traffic
    file, generator and per-layer metric files are in the tree already, so
    it is entries alone) and a backlog cell of another deployment (two
    new JSON files, the entries that name them)."""
    _copy_benchmark(tmp_path)
    (tmp_path / "vlog_tpu").symlink_to(ROOT / "vlog_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}

    cfg = json.loads((ROOT / "benchmark/configs/whisper_small.json"
                      ).read_text())
    cfg["name"] = "whisper_small_b4"
    cfg["deployment"]["env"]["VLOG_ASR_BATCH_WINDOWS"] = "4"
    (tmp_path / "benchmark/configs/whisper_small_b4.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((ROOT / "benchmark/traffic/backlog.json"
                          ).read_text())
    traffic["name"] = "backlog_2clients"
    traffic["rehearsal"]["params"]["first_recording_s"] = [80.0, 55.0]
    (tmp_path / "benchmark/traffic/backlog_2clients.json").write_text(
        json.dumps(traffic))

    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "whisper_small_b4", "source": cfg["source"],
        "file": "benchmark/configs/whisper_small_b4.json", "reduced": [],
        "why": "small at batch 4"})
    bench["workloads"] += [
        {"name": "asr_small_b4_backlog", "config": "whisper_small_b4",
         "traffic": "backlog_2clients", "chips": 1, "why": "a fourth cell"},
        {"name": "asr_small_clips", "config": "whisper_small",
         "traffic": "clips", "chips": 1, "why": "the open loop"}]
    next(m for m in bench["end_to_end"] if m["name"] == "audio_s_per_s")[
        "workloads"].append("asr_small_b4_backlog")
    for name in ("captions_p50_s", "captions_p90_s"):
        bench["end_to_end"].append({
            "name": name, "unit": "s", "better": "lower", "bound": 0.1,
            "source": "host_clock", "workloads": ["asr_small_clips"]})
    for f in sorted((ROOT / "benchmark/layer_metrics").glob("*.clips.json")):
        m = json.loads(f.read_text())
        bench["per_layer"].append({k: m[k] for k in (
            "name", "unit", "better", "source", "layer", "moves")})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    want = {
        ("asr_small_b4_backlog", 0): {"audio_s_per_s", "setup_s"},
        ("asr_small_b4_backlog", 1): {"asr_occupancy.backlog",
                                      "asr_tick_ms.backlog"},
        ("asr_small_clips", 0): {"captions_p50_s", "captions_p90_s",
                                 "setup_s"},
        ("asr_small_clips", 1): {"asr_job_overhead_ms.clips",
                                 "asr_queue_wait_ms.clips",
                                 "asr_tick_ms.clips"},
    }
    for (cell, traced), metrics in want.items():
        proc = run(["benchmark/run.py", "--workload", cell, "--seed", "5",
                    "--seconds", "4", "--trace", str(traced), "--rehearse"],
                   cwd=tmp_path)
        line = last_line(proc)
        assert line["correct"] is True and line["workload"] == cell
        assert set(line["metrics"]) == metrics
        if cell.endswith("clips"):          # lateness is reported
            assert line["extra"]["gen_late_ms_max"] >= 0.0
            assert line["attempted"] >= 1 and line["failed"] == 0
    after = {p: p.read_bytes() for p in before}
    assert after == before
