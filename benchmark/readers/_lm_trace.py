"""Device seconds of a capture by the program's named scopes.

The harness's capture is taken without HLO protos, so a device op is
named by its HLO instruction alone; the program hands out which scope
each instruction of each of its step programs belongs to
(``LmEngine.program_scopes``), and this module books the leaf ops of the
"XLA Ops" line to them by the program that was running
("XLA Modules"). What has no scope, or runs in no known program, is
``unscoped``."""

from __future__ import annotations

import bisect

from harness.trace import _leaf_intervals, _short


def by_scope(planes: list[dict], scopes: dict[str, dict[str, str]]) -> dict:
    """``{scope: seconds}`` averaged over the device planes, largest
    first; ``planes`` as ``harness.trace.load`` returns them."""
    total: dict[str, int] = {}
    n = 0
    for p in planes:
        if not p["name"].startswith("/device:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        if not lines.get("XLA Ops"):
            continue
        n += 1
        runs = sorted((s, e, name.split("(", 1)[0])
                      for s, e, name in lines.get("XLA Modules", ()))
        starts = [r[0] for r in runs]
        for s, e, name in _leaf_intervals(list(lines["XLA Ops"])):
            i = bisect.bisect_right(starts, s) - 1
            program = runs[i][2] if i >= 0 and s < runs[i][1] else ""
            scope = scopes.get(program, {}).get(_short(name), "unscoped")
            total[scope] = total.get(scope, 0) + (e - s)
    n = max(n, 1)
    return {k: v / n / 1e9
            for k, v in sorted(total.items(), key=lambda kv: -kv[1])}
