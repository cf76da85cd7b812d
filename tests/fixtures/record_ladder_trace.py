#!/usr/bin/env python3
"""The chain ladder's first by-stage reading (on the chip, PR 26):

    python3 tests/fixtures/record_ladder_trace.py chiprun_out/ladder_rung.json

One dispatch of the 2160p-to-360p rung alone (one 20-frame chain,
H.264, deblock on, search 8: PERF.md section 5's smallest ladder
program) under a plain ``jax.profiler`` capture, after a first dispatch
that compiled it, reduced by ``vlog_tpu/obs/profiler.py::summarize``:
device seconds by ``ladder.*`` named scope. Keeps the summary, not the
capture. Frames are a moving gradient over noise from a fixed seed, so
the motion search has something to find.
"""

import glob
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def frames(n: int, h: int, w: int, seed: int = 26) -> np.ndarray:
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 64, (h + 64, w + 64), dtype=np.uint8)
    ramp = (np.add.outer(np.arange(h + 64), np.arange(w + 64)) // 24
            ).astype(np.uint8)
    scene = base + ramp
    return np.stack([scene[2 * i:2 * i + h, 3 * i:3 * i + w]
                     for i in range(n)])


def main(out: str) -> None:
    import jax

    from vlog_tpu.obs.profiler import summarize
    from vlog_tpu.parallel.ladder import ladder_chain_program

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_ladder_trace.py: no TPU")
    src_h, src_w, clen = 2160, 3840, 20
    rungs = (("360p", 360, 640, 30),)
    fn, mats = ladder_chain_program(rungs, src_h, src_w, search=8,
                                    mesh=None, deblock=True, pallas=False)
    y = frames(clen, src_h, src_w)[None]
    u = frames(clen, src_h // 2, src_w // 2, seed=27)[None]
    v = frames(clen, src_h // 2, src_w // 2, seed=28)[None]
    qps = {"360p": np.full((1, clen), 30, np.int32)}
    t0 = time.monotonic()
    jax.block_until_ready(fn(y, u, v, mats, qps))
    first_s = time.monotonic() - t0
    log_dir = Path(out + ".tmp")
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    t0 = time.monotonic()
    jax.block_until_ready(fn(y, u, v, mats, qps))
    dispatch_s = time.monotonic() - t0
    t0 = time.monotonic()
    jax.profiler.stop_trace()
    stop_s = time.monotonic() - t0
    pb = glob.glob(str(log_dir / "plugins" / "profile" / "*"
                       / "*.xplane.pb"))[0]
    t0 = time.monotonic()
    got = summarize(pb)
    got.update(first_dispatch_s=first_s, dispatch_s=dispatch_s,
               stop_trace_s=stop_s, summarize_s=time.monotonic() - t0,
               trace_bytes=Path(pb).stat().st_size)
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    Path(out).write_text(json.dumps(got, indent=1) + "\n")
    shutil.rmtree(log_dir, ignore_errors=True)
    print(json.dumps(got)[:4000])


if __name__ == "__main__":
    main(sys.argv[1])
