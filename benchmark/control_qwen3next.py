#!/usr/bin/env python3
"""The controls of ``digest_qwen3next_talks``'s ``correct``, read by the
same numbers at the cell's own size and passed through the cell's own
verdict (``control_xing.py``'s recipe for the ``qwen3next_engine``
driver).

    python3 benchmark/control_qwen3next.py --config qwen3_next_80b_4l --seeds 1 2

For every seed it makes the weights, lets the program serve one clip,
one talk and one stream of the cell's playlist through the cell's
driver (a short window, the logits kept as a run keeps them), frees the
engine and prints one JSON line with, under ``program`` and under each
control, ``compared`` (``drivers/qwen3next_engine.py::compared``) and
``correct``. A control puts other logits, and the tokens that are greedy
under them, in the program's place on the clip and the talk and leaves
the program's on the stream; each has to come out not correct:

- ``control_bf16_compute``: the REFERENCE computed wholly in bfloat16
  (residual stream, the DeltaNet's state and recurrence, norms, router,
  softmax and logits, which the configuration states as float32);
- ``control_state_reset``: the reference with each DeltaNet state zeroed
  at every prefill chunk's first position: what a program that did not
  carry the state from one chunk to the next would serve;
- ``control_no_decay``: the reference with ``g = 0``: a state that
  forgets nothing;
- ``control_no_conv_tail``: the reference with the conv's window started
  afresh at every prefill chunk and at every output position: what a
  program that did not carry the conv tail would serve;
- ``control_ungated_shared``: the reference with the shared expert's
  sigmoid gate dropped.

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

CONTROL_KINDS = ("clip", "talk")


def one_seed(cell, seed: int, seconds: float, rehearse: bool) -> dict:
    from types import SimpleNamespace

    import jax.numpy as jnp
    import numpy as np

    from drivers import qwen3next_engine as drv
    from harness.spec import plugin
    from harness.trace import Tracer

    opts = SimpleNamespace(seed=seed, seconds=seconds, trace=False,
                           rehearse=rehearse, t_start=time.perf_counter())
    r = drv.QwenRun(cell, opts)
    r.plan = plugin("generators", r.traffic["generator"]).generate(
        r.traffic["params"], seed=seed, seconds=seconds)
    r.build()
    _window, records = r.serve(Tracer(False, BENCH_DIR / ".cache" / "none"))
    r.window_records = records
    r.stop_traffic()
    r.free_program()

    chk = r.cfg["check"]
    model = {**r.model, "deployment": r.dep}
    picked = drv.picked_items(r)
    controls = {"control_bf16_compute": {"compute": jnp.bfloat16},
                "control_state_reset": {"reset_state": True},
                "control_no_decay": {"decay": False},
                "control_no_conv_tail": {"conv_carry": False},
                "control_ungated_shared": {"shared_gate": False}}
    rows, by_kind = {}, {}
    for kind, it in sorted(picked.items()):
        rows[kind] = drv.reference_rows(it, r.params, model)
        drv.log(f"reference done: {kind}")
        by_kind[kind] = drv.readings(
            it, rows[kind], it.request.logits.__getitem__, chk)

    def entry(got: dict) -> dict:
        compared = drv.compared(r, got)
        return {"correct": drv.verdict(compared), "compared": compared,
                # per kind, over its compared positions: median, worst
                "errs": {k: [float(np.quantile(g["errs"], q))
                             for q in (0.5, 1.0)] if g["errs"] else None
                         for k, g in got.items()},
                # the median over ALL of a kind's positions
                "all_median": {k: float(np.median(g["all_errs"]))
                               for k, g in got.items()},
                "margin_and_err": {k: g["kept"] for k, g in got.items()}}

    out = {"seed": seed, "tokens": {k: v["tokens"] for k, v in rows.items()},
           "program": entry(by_kind)}
    for name, how in controls.items():
        swapped = dict(by_kind)
        for kind in CONTROL_KINDS:
            if kind not in picked:
                continue
            other = drv.reference_rows(picked[kind], r.params, model, **how)
            by_step = dict(zip(other["steps"], other["logits"]))
            swapped[kind] = drv.readings(
                picked[kind], rows[kind], by_step.__getitem__, chk,
                token_of=lambda s, b=by_step: int(np.argmax(b[s])))
            drv.log(f"{name} done: {kind}")
        out[name] = entry(swapped)
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
            sys.exit("control_qwen3next.py: no TPU (--rehearse for the CPU)")
    for seed in a.seeds:
        print(json.dumps(one_seed(cell, seed, a.seconds, a.rehearse)),
              flush=True)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
