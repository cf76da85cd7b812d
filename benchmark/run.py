#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic, driver and per-layer readers by
the names in ``BENCHMARK.json`` (see ``benchmark/README.md``), fails
without a TPU holding the chips the cell asks for, and prints one JSON
object as the last line of standard output. ``--rehearse`` is for the
sandbox and the tests: CPU, the configuration's tiny ``rehearsal``
widths, and no number under a metric's name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import NoReturn  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))          # harness, drivers, readers, ...


@dataclass
class Opts:
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float


def fail(msg: str, code: int = 3) -> NoReturn:
    print(f"benchmark: {msg}", file=sys.stderr)
    sys.exit(code)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    opts = Opts(a.seed, a.seconds, bool(a.trace), a.rehearse, T_START)

    from harness import spec

    try:
        cell = spec.load_cell(a.workload)
    except spec.SpecError as e:
        fail(str(e), 2)
    if not (ROOT / "vlog_tpu").is_dir():
        fail("the system under test (vlog_tpu/) is not in this directory", 2)
    sys.path.insert(0, str(ROOT))

    if opts.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        # CPU entries do not travel
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        spec.compile_cache_dir()
    import jax

    if not opts.rehearse:
        spec.keep_every_program(jax)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no device: {e}")
    dev = devices[0]
    if opts.rehearse:
        if dev.platform != "cpu":
            fail("--rehearse runs on the CPU only")
    elif dev.platform != "tpu":
        fail(f"no accelerator: JAX reports platform {dev.platform!r}; a cell "
             f"is measured on a TPU or not at all (--rehearse for the CPU)")
    elif len(devices) < cell.chips:
        fail(f"cell {cell.name} asks for {cell.chips} chips, JAX sees "
             f"{len(devices)}")

    from harness.trace import Tracer

    tracer = Tracer(opts.trace and not opts.rehearse,
                    BENCH_DIR / ".cache" / "trace")
    driver = spec.plugin("drivers", cell.config["driver"])
    out = driver.run(cell, opts, tracer)
    trace = tracer.finish()

    def entry(m: dict, value) -> dict:
        return {"value": None if opts.rehearse else value, "unit": m["unit"]}

    metrics = {}
    if not opts.trace:
        for m in cell.end_to_end:
            v = out["end_to_end"].get(m["name"])
            if v is None and not opts.rehearse:
                out["correct"] = False
                out["compared"][f"missing_{m['name']}"] = {"value": 1,
                                                           "limit": 0}
                continue
            metrics[m["name"]] = entry(m, v)
    else:
        ctx = {**out["layer_ctx"], "trace": trace,
               "peaks": None if opts.rehearse
               else spec.peaks_for(dev.device_kind)}
        for m in cell.per_layer:
            v = spec.plugin("readers", m["reader"]).read(ctx, **m["args"])
            if v is not None:       # a reader that finds nothing says nothing
                metrics[m["name"]] = entry(m, v)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips if not opts.rehearse else len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
        out["extra"]["trace"] = {k: trace[k] for k in (
            "modules", "trace_bytes", "stop_trace_s", "devices_traced")}
    line["workload"] = cell.name
    line["seed"] = opts.seed
    line["extra"] = out["extra"]
    line["compared"] = out["compared"]          # last, as the contract asks

    sys.stdout.flush()
    for name, c in out["compared"].items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()
    # job threads that a cut left blocked are daemons; the engine's own
    # thread was joined. Nothing else was started.
    os._exit(0)


if __name__ == "__main__":
    main()
