#!/usr/bin/env python3
"""The controls of ``digest_keye_longform``'s ``correct``, read by the
same numbers at the cell's own size and passed through the cell's own
verdict (``control_lm.py``'s recipe for the ``keye_engine`` driver).

    python3 benchmark/control_keye.py --config keye_vl2_lm_6l --seeds 1 2

For every seed it makes the weights, lets the program serve one clip,
one talk and one stream of the cell's playlist through the cell's
driver (a short window, the logits kept as a run keeps them), frees the
engine and prints one JSON line with, under ``program`` and under each
control, ``compared`` (``drivers/keye_engine.py::compared``) and
``correct``. A control puts other logits, and the tokens that are greedy
under them, in the program's place on the kinds it names and leaves the
program's on the rest; each has to come out not correct:

- ``control_bf16_compute``: the REFERENCE computed wholly in bfloat16
  (residual stream, norms, router, index scores, softmax and logits,
  which the configuration states as float32), on the clip and the talk;
- ``control_dense_attention``: the reference with NO selection (every
  causal key attended), on the talk and the stream: what a program that
  ran the indexer for nothing would serve;
- ``control_newest_keys``: the reference attending the newest 2,048
  keys in place of the learned choice, on the talk and the stream: what
  a program that took the selection for a window would serve.

Two measurements, not controls, each the median, the 90th percentile
and the largest logit error over the talk's positions against the
reference itself: ``one_key_swapped``, the
reference whose every selection has its last key swapped for the best
one left out (what a selection near-tie that falls the other way can
move: ``select_eps`` is set from it, PERF.md section 6), and
``float32_operands``, the reference with the products' activations left
in float32 (how far a pass that does not round where the configuration
states bfloat16 lies from one that does: why the reference rounds).

Not part of a benchmark run. ``--rehearse`` runs it at the rehearsal
widths on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent))


def one_seed(cell, seed: int, seconds: float, rehearse: bool) -> dict:
    from types import SimpleNamespace

    import jax.numpy as jnp
    import numpy as np

    from drivers import keye_engine as drv
    from harness.spec import plugin
    from harness.trace import Tracer

    opts = SimpleNamespace(seed=seed, seconds=seconds, trace=False,
                           rehearse=rehearse, t_start=time.perf_counter())
    r = drv.KeyeRun(cell, opts)
    r.plan = plugin("generators", r.traffic["generator"]).generate(
        r.traffic["params"], seed=seed, seconds=seconds)
    r.build()
    _window, records = r.serve(Tracer(False, BENCH_DIR / ".cache" / "none"))
    r.window_records = records
    r.stop_traffic()
    r.free_program()

    chk = r.cfg["check"]
    picked = drv.picked_items(r)
    controls = {
        "control_bf16_compute": (("clip", "talk"),
                                 {"compute": jnp.bfloat16}),
        "control_dense_attention": (("talk", "stream"), {"select": "dense"}),
        "control_newest_keys": (("talk", "stream"), {"select": "newest"})}
    rows, by_kind = {}, {}
    for kind, it in sorted(picked.items()):
        rows[kind] = drv.reference_rows(it, r.params, r.model)
        by_kind[kind] = drv.readings(
            it, rows[kind], it.request.logits.__getitem__, chk)

    def entry(readings: dict) -> dict:
        compared = drv.compared(r, readings)
        return {"correct": drv.verdict(compared), "compared": compared,
                # per kind, over ALL its positions: median, 90th, worst
                "errs": {k: [float(np.quantile(g["all_errs"], q))
                             for q in (0.5, 0.9, 1.0)]
                         for k, g in readings.items()}}

    out = {"seed": seed, "tokens": {k: v["tokens"] for k, v in rows.items()},
           "program": entry(by_kind)}
    for name, (kinds, how) in controls.items():
        swapped = dict(by_kind)
        for kind in kinds:
            if kind not in picked:
                continue
            other = drv.reference_rows(picked[kind], r.params, r.model,
                                       **how)
            by_step = dict(zip(other["steps"], other["logits"]))
            swapped[kind] = drv.readings(
                picked[kind], rows[kind], by_step.__getitem__, chk,
                token_of=lambda s, b=by_step: int(np.argmax(b[s])))
            drv.log(f"{name} done: {kind}")
        out[name] = entry(swapped)
    if "talk" in picked:
        from reference.keye_ref import logit_error

        for name, how in (("one_key_swapped", {"select": "swapped"}),
                          ("float32_operands", {"operands": "float32"})):
            other = drv.reference_rows(picked["talk"], r.params, r.model,
                                       **how)
            errs = [logit_error(a, b) for a, b in zip(
                other["logits"], rows["talk"]["logits"])]
            out[name] = [float(np.quantile(errs, q)) for q in (0.5, 0.9, 1.0)]
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    from harness import spec

    bench = spec.load_bench()
    name = next(w["name"] for w in bench["workloads"]
                if w["config"] == a.config)
    cell = spec.load_cell(name)
    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        spec.compile_cache_dir()
    import jax

    if not a.rehearse:
        spec.keep_every_program(jax)
        if jax.devices()[0].platform != "tpu":
            sys.exit("control_keye.py: no TPU (--rehearse for the CPU)")
    for seed in a.seeds:
        print(json.dumps(one_seed(cell, seed, a.seconds, a.rehearse)),
              flush=True)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
