"""Open loop: bursts of short clips, due on a schedule whatever the
system does.

Parameters (the traffic file's ``params``):

- ``offered_windows_per_s``: the load, in 30 s windows a second, fixed
  in the file (four fifths of the knee a sweep on the chip found);
- ``cycle_s``: length of the schedule; a run longer than a cycle repeats
  it;
- ``burst_mean`` / ``burst_max``: burst sizes are geometric with that
  mean, cut to 1..``burst_max`` (a folder dropped into the uploader);
- ``clip_s``: ``[lo, hi]``, clip lengths uniform between them, so a clip
  is 1 window up to 30 s and 2 windows beyond (25 s stride);
- ``schedule_seed``: fixes the ONE schedule every run uses.

Bursts are a Poisson process conditioned on their number: as many
bursts as carry ``offered_windows_per_s * cycle_s`` windows, at uniform
times in the cycle. The run's seed deals the clip lengths of a burst
anew among its clips of the same window count (and makes the audio and
the weights), so every seed offers the same bursts at the same
instants, with one- and two-window clips in the same places: the same
set of sizes and arrivals, in another order. Dealing lengths across
window counts moved `captions_p50_s` from 12.5 to 14.4 s between two
seeds with identical ticks (my chip run, PR 25). A schedule drawn afresh from every seed would hold 6 to
10 bursts and swing the offered load by a third from seed to seed, and
one that only moved the bursts would move every latency by up to a tick
(9 s against a median of 15 s).
"""

from __future__ import annotations

import numpy as np

WINDOW_S = 30.0
STRIDE_S = 25.0


def windows_of(clip_s: float) -> int:
    n, t = 1, 0.0
    while t + WINDOW_S < clip_s:
        t += STRIDE_S
        n += 1
    return n


def base_schedule(params: dict) -> list[dict]:
    """The cycle: ``[{"due_s", "audio_s", "burst"}]`` sorted by time."""
    rng = np.random.default_rng([int(params["schedule_seed"]), 202])
    target = params["offered_windows_per_s"] * params["cycle_s"]
    lo, hi = params["clip_s"]
    p = 1.0 / params["burst_mean"]
    bursts: list[list[float]] = []
    total = 0
    while total < target:
        size = int(min(max(rng.geometric(p), 1), params["burst_max"]))
        clips = [float(np.round(rng.uniform(lo, hi), 1))
                 for _ in range(size)]
        need = target - total
        kept = []
        for c in clips:          # stop at the target, not a burst beyond it
            if need <= 0:
                break
            kept.append(c)
            need -= windows_of(c)
        bursts.append(kept)
        total += sum(windows_of(c) for c in kept)
    times = np.sort(rng.uniform(0.0, params["cycle_s"], len(bursts)))
    jobs = []
    for b, (t, clips) in enumerate(zip(times, bursts)):
        for c in clips:
            jobs.append({"due_s": float(t), "audio_s": c, "burst": b})
    return jobs


def generate(params: dict, *, seed: int, seconds: float) -> dict:
    cycle = float(params["cycle_s"])
    base = base_schedule(params)
    rng = np.random.default_rng([int(seed), 303])
    groups: dict[tuple[int, int], list[dict]] = {}
    for j in base:
        groups.setdefault((j["burst"], windows_of(j["audio_s"])),
                          []).append(j)
    jobs = []
    k = 0
    while k * cycle < seconds:
        shuffled = {key: [members[int(i)] for i in
                          rng.permutation(len(members))]
                    for key, members in groups.items()}
        for j in base:      # same slots; lengths dealt within the group
            pick = shuffled[(j["burst"], windows_of(j["audio_s"]))].pop()
            due = j["due_s"] + k * cycle
            if due < seconds:
                jobs.append({"due_s": due, "audio_s": pick["audio_s"],
                             "burst": j["burst"]})
        k += 1
    jobs.sort(key=lambda j: j["due_s"])         # stable: keeps the order
    return {"mode": "open", "jobs": jobs,
            "offered_windows_per_s": sum(windows_of(j["audio_s"])
                                         for j in jobs) / seconds}
