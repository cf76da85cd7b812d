"""Closed loop: ``clients`` callers, each submitting its next recording
when the last one is done.

Parameters (the traffic file's ``params``):

- ``clients``: how many callers wait at once;
- ``recording_s``: length of every recording after a client's first;
- ``first_recording_s``: one length per client for its FIRST recording,
  so that the clients do not finish in lockstep and the queue never
  drains between rounds. The seed decides which client gets which.

The plan is the same multiset of sizes for every seed, in another
order; tone and noise of the audio come from the seed in the driver.
"""

from __future__ import annotations

import numpy as np


def generate(params: dict, *, seed: int, seconds: float) -> dict:
    rng = np.random.default_rng([int(seed), 101])
    first = list(params["first_recording_s"])
    if len(first) != params["clients"]:
        raise ValueError("first_recording_s needs one length per client")
    order = rng.permutation(len(first))
    return {
        "mode": "closed",
        "clients": [{"first_s": float(first[i]),
                     "then_s": float(params["recording_s"])}
                    for i in order],
    }
