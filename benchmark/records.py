#!/usr/bin/env python3
"""One run of a cell that also keeps what the program recorded of itself.

    python3 benchmark/records.py --out chiprun_out/records/small_7.json \\
        --workload asr_small_backlog --seed 7 --seconds 51 --trace 0

Everything else is ``run.py``. Beside the result line it writes, to
``--out``: the window's tick records (``engine.batch_log`` entries:
phases, gap, build seconds of every tick), each job's ``stats_out`` and
when it began and ended (so a slow tick can be set beside the language
passes in flight), and, with ``--trace 1``, the program's own reduction
of the capture (``vlog_tpu/obs/profiler.py::summarize``: device seconds
by named scope, idle gaps by the program's spans) with how long that
reduction took. It is how PERF.md's by-scope tables and slow-tick
findings are read. Never run by the driver; a program without the
record or the reduction leaves those parts out.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))


def main() -> None:
    if "--out" not in sys.argv:
        sys.exit(__doc__)
    i = sys.argv.index("--out")
    out_path = Path(sys.argv[i + 1])
    del sys.argv[i:i + 2]
    out_path.parent.mkdir(parents=True, exist_ok=True)
    kept: dict = {}

    def write() -> None:
        tmp = out_path.with_suffix(".tmp")
        tmp.write_text(json.dumps(kept, indent=1, default=str))
        tmp.rename(out_path)

    import run as bench_run
    from harness import spec, trace

    plugin = spec.plugin

    def keeping_plugin(kind, name):
        module = plugin(kind, name)
        if kind != "drivers" or hasattr(module.run, "__wrapped__"):
            return module
        drive = module.run

        def run(cell, opts, tracer):
            out = drive(cell, opts, tracer)
            ctx = out["layer_ctx"]
            t0 = ctx["window"]["t0"]
            kept.update({
                "workload": cell.name, "seed": opts.seed,
                "correct": out["correct"], "end_to_end": out["end_to_end"],
                "window": ctx["window"],
                "tick_records": ctx["batch_log"],
                "jobs": [{"job": j.job_id, "status": j.status,
                          "began_s": j.start_t - t0,
                          "ended_s": j.end_t - t0 if j.end_t else None,
                          "first_submit_s": (j.first_submit_t - t0
                                             if j.first_submit_t else None),
                          "stats": j.stats} for j in ctx["jobs"]]})
            write()
            return out

        run.__wrapped__ = drive
        module.run = run
        return module

    spec.plugin = keeping_plugin

    reduce = trace.reduce

    def keeping_reduce(path):
        try:
            from vlog_tpu.obs.profiler import summarize
        except ImportError:
            summarize = None
        if summarize is not None:
            t0 = time.monotonic()
            kept["summary"] = summarize(path)
            kept["summarize_s"] = time.monotonic() - t0
            write()
        return reduce(path)

    trace.reduce = keeping_reduce
    bench_run.main()


if __name__ == "__main__":
    main()
