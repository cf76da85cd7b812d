#!/usr/bin/env python3
"""The controls of the transcript cell's ``correct``, read by the same
numbers at the cell's own size and passed through the cell's own verdict.

    python3 benchmark/control_lm.py --config trinity_mini_6l --seeds 1 2

For every seed it makes the weights, lets the program serve one clip,
one talk and one stream of the cell's playlist through the cell's
driver (a short window, the logits kept as a run keeps them), frees the
engine and prints one JSON line with, under ``program`` and under each
control, ``compared`` (the numbers a run of the cell prints, each beside
its limit: ``drivers/lm_engine.py::compared_of``) and ``correct`` (the
harness's ``verdict`` of them). A control puts other logits, and the
tokens that are greedy under them, in the program's place on the kinds
it names and leaves the program's on the rest; each has to come out
not correct:

- ``control_bf16_compute``: the REFERENCE computed wholly in bfloat16
  (residual stream, norms, router, softmax and logits, which the
  configuration states as float32: the nearest precision below it), on
  the clip and the talk;
- ``control_int8_weights``: the reference on weights rounded to int8
  (per output channel, symmetric), on the clip and the talk;
- ``control_no_window_mask``: the reference WITHOUT the window mask, on
  the stream: what a program that treated the window as nothing would
  serve.

``benchmark/control.py`` is Whisper's and stays as it is. Not part of a
benchmark run. ``--rehearse`` runs it at the rehearsal widths on the
CPU.
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


def _int8(w):
    """A matrix rounded to 8 bits per output channel and back."""
    import jax.numpy as jnp

    if w.ndim < 2:
        return w
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127.0 + 1e-30
    return (jnp.round(w32 / scale) * scale).astype(w.dtype)


class Int8Params:
    """The weights with every matrix rounded to int8, a leaf group at a
    time as the reference asks for it: a second whole copy of 8.6 GB
    does not fit beside the first."""

    class _Layers:
        def __init__(self, layers):
            self._layers = layers

        def __getitem__(self, li):
            return {k: _int8(v) for k, v in self._layers[li].items()}

    def __init__(self, params: dict):
        self._params = params

    def __getitem__(self, key):
        if key == "layers":
            return self._Layers(self._params["layers"])
        return _int8(self._params[key])


def one_seed(cell, seed: int, seconds: float, rehearse: bool) -> dict:
    from types import SimpleNamespace

    from drivers import lm_engine as drv
    from harness.spec import plugin
    from harness.trace import Tracer

    opts = SimpleNamespace(seed=seed, seconds=seconds, trace=False,
                           rehearse=rehearse, t_start=time.perf_counter())
    r = drv.Run(cell, opts)
    r.plan = plugin("generators", r.traffic["generator"]).generate(
        r.traffic["params"], seed=seed, seconds=seconds)
    r.build()
    _window, records = r.serve(Tracer(False, BENCH_DIR / ".cache" / "none"))
    r.window_records = records
    r.stop_traffic()
    r.free_program()
    import jax.numpy as jnp
    import numpy as np

    chk = r.cfg["check"]
    picked = drv.picked_items(r)
    controls = {
        "control_bf16_compute": (("clip", "talk"), r.params,
                                 {"compute": jnp.bfloat16}),
        "control_int8_weights": (("clip", "talk"), Int8Params(r.params), {}),
        "control_no_window_mask": (("stream",), r.params,
                                   {"off": ("window_mask",)})}
    rows, by_kind = {}, {}
    for kind, it in sorted(picked.items()):
        rows[kind] = drv.reference_rows(it, r.params, r.model)
        by_kind[kind] = drv.readings(
            it, rows[kind], it.request.logits.__getitem__, chk)

    def entry(readings: dict) -> dict:
        compared = drv.compared_of(r, readings)
        return {"correct": drv.verdict(compared), "compared": compared}

    out = {"seed": seed, "tokens": {k: v["tokens"] for k, v in rows.items()},
           "program": entry(by_kind)}
    for name, (kinds, params, how) in controls.items():
        swapped = dict(by_kind)
        for kind in kinds:
            if kind not in picked:
                continue
            other = drv.reference_rows(picked[kind], params, r.model, **how)
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
            sys.exit("control_lm.py: no TPU (--rehearse for the CPU)")
    for seed in a.seeds:
        print(json.dumps(one_seed(cell, seed, a.seconds, a.rehearse)),
              flush=True)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
