#!/usr/bin/env python3
"""Drive a rehearsal of a transcript cell with the timed path broken
underneath, to show that ``correct`` comes out false.

    python3 benchmark/faults_lm.py --fault expert --workload <name> --seed <n> --seconds <s>

- ``expert``: the engine serves with routed expert 0's down projection
  zeroed in every expert layer (a weight lost on the way to the device);
  the reference keeps the true weights.

``benchmark/faults.py`` is the Whisper cells'; this is the same tool for
the ``lm_engine`` driver. A test tool: no benchmark run uses it.
"""

from __future__ import annotations

import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent))


def break_engine(run, fault: str) -> None:
    if fault != "expert":
        sys.exit(f"faults_lm.py: unknown fault {fault!r}")
    broken = dict(run.params)
    broken["layers"] = [
        {**lp, "e_down": lp["e_down"].at[0].set(0)} if "e_down" in lp
        else lp for lp in run.params["layers"]]
    run.engine.assets.params = broken     # read at every step's call


def main() -> None:
    if "--fault" not in sys.argv:
        sys.exit(__doc__)
    i = sys.argv.index("--fault")
    fault = sys.argv[i + 1]
    del sys.argv[i:i + 2]
    sys.argv += ["--rehearse"]
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    import run as bench_run
    from drivers import lm_engine

    build = lm_engine.Run.build

    def build_then_break(self):
        build(self)
        break_engine(self, fault)

    lm_engine.Run.build = build_then_break
    bench_run.main()


if __name__ == "__main__":
    main()
