"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under ``benchmark/``; this module
is the only place that knows where they live, and it checks the names
against the characters the contract allows before anything runs.
"""

from __future__ import annotations

import importlib
import json
import os
import re
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent     # .../benchmark
ROOT = BENCH_DIR.parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    pass


def compile_cache_dir() -> Path:
    """One persistent compilation cache for this checkout, at a fixed
    path inside it (the path is part of the cache's key); a directory
    given from outside wins, and the program sets none of its own then.
    Call before ``jax`` is imported."""
    cache = Path(os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                       str(BENCH_DIR / ".cache" / "xla")))
    cache.mkdir(parents=True, exist_ok=True)
    return cache


def keep_every_program(jax) -> None:
    """Cache every program and evict none: a cell's programs (four beam
    programs, the language pass's 600 eager ones, the reference) pass the
    192 MiB that the chip tool's machines set as
    JAX_COMPILATION_CACHE_MAX_SIZE, and under that cap every run evicted
    what the next one needed and compiled for 5 minutes (my chip run,
    PR 25). Without it only a checkout's first run compiles, and less is
    written, not more."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: dict            # the configuration file
    traffic_name: str
    traffic: dict           # the traffic file
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]   # the same, each with its layer_metrics file
    bench: dict             # all of BENCHMARK.json


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"{path.relative_to(ROOT)}: no such file") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path.relative_to(ROOT)}: {e}") from None


def load_bench(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_bench(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SpecError(f"no workload {name!r} in BENCHMARK.json ({known})")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {name}: no config {entry['config']!r}")
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(root / "benchmark" / "traffic"
                        / f"{entry['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layers = []
    for m in bench["per_layer"]:
        if not _applies(m, name) or m["moves"] not in reported:
            continue
        spec = load_json(root / "benchmark" / "layer_metrics"
                         / f"{m['name']}.json")
        layers.append({**m, **{k: spec[k] for k in ("reader",)},
                       "args": spec.get("args", {})})
    return Cell(name=name, chips=entry["chips"], why=entry["why"],
                config_name=entry["config"], config=config,
                traffic_name=entry["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=layers, bench=bench)


def plugin(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (drivers, generators,
    readers), found by the name a data file gives."""
    if not NAME.match(name):
        raise SpecError(f"bad {kind} name {name!r}")
    try:
        return importlib.import_module(f"{kind}.{name}")
    except ModuleNotFoundError as e:
        if e.name == f"{kind}.{name}":
            raise SpecError(f"benchmark/{kind}/{name}.py: no such file") \
                from None
        raise


def peaks_for(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"benchmark/peaks.json; add it with its source")
    return table["devices"][device_kind]


def check_names(bench: dict) -> list[str]:
    """Every name, unit and line of BENCHMARK.json against the limits
    of the contract; returns the faults found."""
    bad = []

    def name(x, where):
        if not isinstance(x, str) or not NAME.match(x):
            bad.append(f"{where}: bad name {x!r}")

    def line(x, where):
        if (not isinstance(x, str) or not 1 <= len(x) <= 200
                or "\n" in x or "\t" in x):
            bad.append(f"{where}: not one line of 1 to 200 characters")

    seen: set[str] = set()
    for c in bench["configs"]:
        name(c["name"], "config")
        line(c["source"], f"config {c['name']}.source")
        line(c["why"], f"config {c['name']}.why")
        for k in c["reduced"]:
            name(k, f"config {c['name']}.reduced")
    for w in bench["workloads"]:
        name(w["name"], "workload")
        name(w["traffic"], f"workload {w['name']}.traffic")
        line(w["why"], f"workload {w['name']}.why")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            name(m["name"], group)
            if m["name"] in seen:
                bad.append(f"{group}: {m['name']} named twice")
            seen.add(m["name"])
            if not UNIT.match(m["unit"]):
                bad.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{m['name']}: better={m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"{m['name']}: source={m['source']!r}")
            if group == "per_layer":
                line(m["layer"], f"{m['name']}.layer")
    return bad
