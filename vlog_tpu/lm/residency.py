"""One model engine resident on a worker at a time.

The transcript model's weights and a Whisper beam program do not share
one chip's memory, so whoever builds an engine first makes room: it
waits until the other plane's engine is idle (a job boundary: nothing
queued, no lease held) and tears it down. Both planes call
:func:`make_room` from their ``get_engine`` before they load weights;
neither imports the other at module level.
"""

from __future__ import annotations

import importlib
import sys
import time

# plane -> the module that holds its peek_engine / reset_engine
_PLANES = {"asr": "vlog_tpu.asr.engine", "lm": "vlog_tpu.lm.engine"}


def make_room(plane: str, *, timeout_s: float = 3600.0,
              poll_s: float = 0.05) -> list[str]:
    """Tear down every OTHER plane's engine once it is idle; returns the
    planes that were evicted. Raises ``TimeoutError`` if one stays busy
    (its jobs hold the chip; the caller's job fails and is retried)."""
    evicted = []
    for other, module in _PLANES.items():
        if other == plane or module not in sys.modules:
            continue        # a plane never imported has no engine
        mod = importlib.import_module(module)
        engine = mod.peek_engine()
        if engine is None:
            continue
        deadline = time.monotonic() + timeout_s
        while engine.active():
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"the {other} engine stayed busy for {timeout_s:.0f} s")
            time.sleep(poll_s)
        mod.reset_engine()
        if other == "asr":
            from vlog_tpu.asr.decode import kv_pool

            kv_pool.reset()
        evicted.append(other)
    return evicted
