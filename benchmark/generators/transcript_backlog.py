"""Closed loop over ONE playlist of transcripts that is the same for
every seed.

PR 28's cell was refused because its work differed by seed (ledger:
101 and 103 s/s between pairs). Here the playlist is drawn ONCE, from
the traffic file's ``schedule_seed``, and shuffled once; ``--seed`` is
not read at all (it makes the weights and the token ids, in the
driver). ``clients`` callers take the next recording from one shared
cursor when their last one is done, and the playlist repeats.

Parameters: ``classes`` (``count`` recordings each, ``audio_s`` uniform
in a range, a forced ``output_tokens``), ``instruction_tokens`` (the
same instruction before every transcript), ``tokens_per_audio_s`` as a
ratio ``[a, b]`` (a recording of ``s`` seconds is ``round(s * a / b)``
transcript tokens).

The plan: ``{"mode": "closed", "clients", "playlist": [{"kind",
"audio_s", "prompt_tokens", "output_tokens"}], "open_when_finished"}``.
:func:`simulate` is the step engine's schedule on the host (every
resident decoding row a step, one prefill chunk of one request), for
the checks: the same plan gives the same work in every window of steps.
"""

from __future__ import annotations

import numpy as np


def generate(params: dict, *, seed: int, seconds: float) -> dict:
    del seed, seconds           # the playlist is the same for every run
    rng = np.random.default_rng([int(params["schedule_seed"]), 2929])
    a, b = params["tokens_per_audio_s"]
    items = []
    for cls in params["classes"]:
        lo, hi = cls["audio_s"]
        for audio in rng.uniform(lo, hi, int(cls["count"])):
            audio = round(float(audio), 1)
            items.append({
                "kind": cls["name"], "audio_s": audio,
                "prompt_tokens": int(params["instruction_tokens"])
                + int(round(audio * a / b)),
                "output_tokens": int(cls["output_tokens"])})
    order = rng.permutation(len(items))
    return {"mode": "closed", "clients": int(params["clients"]),
            "playlist": [items[i] for i in order],
            "open_when_finished": int(params["open_when_finished"])}


def credit_per_token(item: dict) -> float:
    """Audio seconds one prompt or output token of a recording carries:
    its audio spread evenly over all of its tokens."""
    return item["audio_s"] / (item["prompt_tokens"] + item["output_tokens"])


def simulate(plan: dict, *, steps: int, rows: int, chunk: int) -> list[dict]:
    """The first ``steps`` steps of the engine's schedule under this
    plan: per step the prefill tokens, the decoding rows, the playlist
    indices that got a token and the audio seconds credited."""
    playlist = plan["playlist"]
    cursor = 0
    waiting: list[dict] = []
    prefilling = None
    decoding: list[dict] = []

    def take():
        nonlocal cursor
        item = playlist[cursor % len(playlist)]
        cursor += 1
        return {"item": item, "index": cursor - 1, "prefilled": 0,
                "emitted": 0}

    for _ in range(plan["clients"]):
        waiting.append(take())
    out = []
    for _ in range(steps):
        decoding = [r for r in decoding
                    if r["emitted"] < r["item"]["output_tokens"]]
        if prefilling is None and waiting \
                and len(decoding) < rows:
            prefilling = waiting.pop(0)
        step = {"prefill_tokens": 0, "decode_rows": len(decoding),
                "emitted": [], "audio_s": 0.0}
        for r in decoding:
            r["emitted"] += 1
            step["emitted"].append(r["index"])
            step["audio_s"] += credit_per_token(r["item"])
        if prefilling is not None:
            r = prefilling
            n = min(chunk, r["item"]["prompt_tokens"] - r["prefilled"])
            r["prefilled"] += n
            step["prefill_tokens"] = n
            step["audio_s"] += n * credit_per_token(r["item"])
            if r["prefilled"] >= r["item"]["prompt_tokens"]:
                r["emitted"] = 1
                step["emitted"].append(r["index"])
                step["audio_s"] += credit_per_token(r["item"])
                decoding.append(r)
                prefilling = None
        # a finished request's client submits its next one at once
        for r in decoding:
            if r["emitted"] >= r["item"]["output_tokens"] \
                    and not r.get("replaced"):
                r["replaced"] = True
                waiting.append(take())
        out.append(step)
    return out
