"""Shared by the benchmark's process-level tests: every run is a
process of its own, as the driver starts one, on the CPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def env():
    e = {k: v for k, v in os.environ.items()
         if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    e["JAX_PLATFORMS"] = "cpu"
    return e


def run(args, cwd=ROOT, timeout=600):
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env(),
                          capture_output=True, text=True, timeout=timeout)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
