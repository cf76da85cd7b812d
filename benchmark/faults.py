#!/usr/bin/env python3
"""Drive a rehearsal run with the timed path broken underneath, to show
that ``correct`` comes out false (step 3 of "How correct is decided").

    python3 benchmark/faults.py --fault token --workload <name> --seed <n> --seconds <s>

Faults a serving cell can have (a token or an answer altered where it is
produced; the others of the list belong to training and to several
chips):

- ``token``: the model step returns the second served token of every
  window changed to a text id, before anything reads it;
- ``cue``: the engine's parse hands back, for every window, a first cue
  whose text differs from the tokens served.

It skips the look for a chip (``--rehearse``: CPU, tiny widths) and runs
everything else of ``run.py``. A test tool: no benchmark run uses it.
"""

from __future__ import annotations

import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent))


def plant(fault: str) -> None:
    from vlog_tpu.asr import decode

    if fault == "token":
        inner = decode.generate_batch

        def generate_batch(assets, mel, **kw):
            toks, nsp = inner(assets, mel, **kw)
            toks = toks.copy()
            # a text id where the grammar wants a timestamp's partner, in
            # every row: the logit check reads a sample of the windows
            toks[:, 1] = 1234
            return toks, nsp

        decode.generate_batch = generate_batch
    elif fault == "cue":
        inner = decode.parse_segments

        def parse_segments(tokens, st, **kw):
            segs = inner(tokens, st, **kw)
            if segs:
                segs[0].token_ids = list(segs[0].token_ids) + [4321]
            return segs

        decode.parse_segments = parse_segments
    else:
        sys.exit(f"faults.py: unknown fault {fault!r}")


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
    from drivers import asr_engine

    build = asr_engine.Run.build

    def build_then_break(self):
        plant(fault)            # under the driver's own spans and capture
        build(self)

    asr_engine.Run.build = build_then_break
    bench_run.main()


if __name__ == "__main__":
    main()
