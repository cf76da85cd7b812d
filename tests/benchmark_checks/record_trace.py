#!/usr/bin/env python3
"""How ``recorded_trace.json`` was made (on the chip, once, PR 25):

    python3 tests/benchmark_checks/record_trace.py chiprun_out/recorded

Two runs of a small jitted scan with a sleep between them, under the
benchmark's own tracer and spans; the planes as ``harness/trace.py``
loads them (names cut to 100 characters) and what its reduction gives.
The test reduces the recorded planes again and compares.
"""

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from harness import trace
    from harness.spans import Recorder

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace.py: no TPU")

    @jax.jit
    def step(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), ()
        return jax.lax.scan(body, x, None, length=24)[0]

    x = jnp.ones((256, 512))
    w = jnp.full((512, 512), 0.01)
    step(x, w).block_until_ready()
    rec = Recorder()
    tracer = trace.Tracer(True, Path(out + ".tmp"))
    tracer.start()
    rec.annotate = True
    with rec.span("generate"):
        step(x, w).block_until_ready()
    with rec.span("parse"):
        time.sleep(0.004)
    with rec.span("generate"):
        step(x, w).block_until_ready()
    tracer.stop_now()
    import glob

    pb = glob.glob(out + ".tmp/plugins/profile/*/*.xplane.pb")[0]
    planes = trace.load(pb)
    for p in planes:
        for ln in p["lines"]:
            ln["events"] = [(s, e, n[:100]) for s, e, n in ln["events"]]
    got = trace.reduce_planes(planes)
    Path(out + "_trace.json").write_text(json.dumps(planes))
    Path(out + "_trace.expect.json").write_text(json.dumps(got, indent=1))
    tracer.finish()
    print(json.dumps(got)[:2000])


if __name__ == "__main__":
    main(sys.argv[1])
