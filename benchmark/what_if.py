#!/usr/bin/env python3
"""One run of a cell with values of its configuration or traffic file
changed for that run alone.

    python3 benchmark/what_if.py --set traffic.params.offered_windows_per_s=0.9 \
        --workload asr_small_clips --seed 5 --seconds 51 --trace 0
    python3 benchmark/what_if.py --set config.deployment.matmul_precision=default \
        --workload asr_small_backlog --seed 5 --seconds 51 --trace 0

Everything else is ``run.py``. It is how an open-loop cell's knee is
found (the highest offered rate whose backlog at the close one tick
drains; four fifths of it goes into the traffic file as a number) and
how a what-if of PERF.md was read. Never run by the driver; a number it
prints is no benchmark result.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))


def main() -> None:
    changes = []
    while "--set" in sys.argv:
        i = sys.argv.index("--set")
        path, _, raw = sys.argv[i + 1].partition("=")
        try:
            value = json.loads(raw)
        except json.JSONDecodeError:
            value = raw
        changes.append((path.split("."), value))
        del sys.argv[i:i + 2]
    if not changes:
        sys.exit(__doc__)
    import run as bench_run
    from harness import spec

    load = spec.load_cell

    def load_cell(name, *a, **kw):
        cell = load(name, *a, **kw)
        for (head, *keys), value in changes:
            node = {"config": cell.config, "traffic": cell.traffic}[head]
            for k in keys[:-1]:
                node = node[k]
            node[keys[-1]] = value
        return cell

    spec.load_cell = load_cell
    bench_run.main()


if __name__ == "__main__":
    main()
