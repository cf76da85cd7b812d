#!/usr/bin/env python3
"""The control of ``correct``: the reference in the nearest precision
below the configuration's (bfloat16 for float32), put in the program's
place, read by the same numbers at the cell's own size.

    python3 benchmark/control.py --config whisper_small --seeds 1 2 3

For every seed it makes the weights and one full bucket of windows,
lets the program's model step (``generate_batch``, the cell's bucket and
beam) serve tokens for them, and prints one JSON line with

- ``program``: ``nospeech_logp_err`` and ``beam_rank_gap`` of what the
  program served (the lower readings; the cells' own runs print the same
  numbers for every seed they run);
- ``program_quant_bf16`` / ``program_quant_int8``: the same with
  ``VLOG_WHISPER_QUANT`` set, the program's own lower-precision paths;
- ``fault_token_swapped``: ``beam_rank_gap`` where one served token of
  each window is swapped for another that the rules allow (the fault
  "a token altered where it is produced"), smallest and median;
- ``control_bf16``: ``nospeech_logp_err`` of the bfloat16 reference, and
  ``first_choice_gap``: at each position of the same prompts and tokens,
  how far the token that bfloat16 puts first lies below the float32
  reference's best (widest, and the share of positions where they
  differ).

Not part of a benchmark run. ``--rehearse`` runs it at the rehearsal
widths on the CPU (the test under tests/benchmark_checks does).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR.parent))


def readings(cfg: dict, seed: int, rows: int, beam: int) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from drivers.asr_engine import Audio, build_assets, decode_quant
    from models.whisper_weights import make_params
    from reference import whisper_ref as ref
    from vlog_tpu.asr import decode, mel

    voc = cfg["vocab"]
    params = make_params(cfg, seed)
    audio = Audio(seed)
    batch = np.stack([ref.pad_or_trim(audio.make(30.0))
                      for _ in range(rows)])

    _assets, vocab = build_assets(cfg, params, name="control")

    def serve(quant: str):
        assets, _ = build_assets(cfg, decode_quant(params, quant),
                                 name="control")
        feats = mel.log_mel_spectrogram(batch, n_mels=cfg["num_mel_bins"])
        return decode.generate_batch(assets, feats,
                                     language=voc["language"], beam=beam)

    toks, nsp = serve("f32")
    _toks_q, nsp_q = serve("bf16")
    _toks_i, nsp_i = serve("int8")
    decode.kv_pool.reset()
    feats = ref.log_mel(jnp.asarray(batch), n_mels=cfg["num_mel_bins"])
    seq = np.concatenate([np.tile(vocab.prompt, (rows, 1)), toks], axis=1)
    block = cfg["check"].get("block", 4)
    lg = ref.logits(params, cfg, feats, seq, block=block)
    lg_low = ref.logits(params, cfg, feats, seq, dtype=jnp.bfloat16,
                        block=block)

    def rms(xs):
        return math.sqrt(sum(x * x for x in xs) / len(xs))

    ref_lp = [ref.no_speech_logp(r, vocab) for r in lg]
    gaps = [ref.served_gaps(r, t, vocab, beam,
                            rule_tol=cfg["check"]["rule_tol"])
            for r, t in zip(lg, toks)]
    first = [ref.first_choice_gaps(r, lo, t, vocab)
             for r, lo, t in zip(lg, lg_low, toks)]
    flat = [g for row in first for g in row]
    # the fault of a serving cell, read by the same number: one served
    # token of each window swapped for another that the rules allow
    rng = np.random.default_rng([seed, 606])
    plen = len(vocab.prompt)
    swapped = []
    for r, t in zip(lg, toks):
        t = t.tolist()
        n = t.index(vocab.eot) if vocab.eot in t else len(t)
        step = int(rng.integers(0, max(n, 1)))
        ok, _ = ref.allowed_mask(t[:step], step, r[plen - 1 + step], vocab)
        ok[t[step]] = False
        other = int(rng.choice(np.flatnonzero(ok)))
        ok[t[step]] = True
        swapped.append(ref.rank_gap(other, r[plen - 1 + step], ok, beam))
    return {
        "seed": seed, "rows": rows, "beam": beam,
        "served_tokens": sum(len(g) for g in gaps),
        "program": {
            "nospeech_logp_err": rms([math.log(p) - r
                                      for p, r in zip(nsp, ref_lp)]),
            "beam_rank_gap": max(max(g) for g in gaps)},
        "program_quant_bf16": {
            "nospeech_logp_err": rms([math.log(p) - r
                                      for p, r in zip(nsp_q, ref_lp)]),
            "same_nsp_as_f32": bool((np.asarray(nsp) == np.asarray(nsp_q)
                                     ).all())},
        "program_quant_int8": {
            "nospeech_logp_err": rms([math.log(p) - r
                                      for p, r in zip(nsp_i, ref_lp)])},
        "fault_token_swapped": {"beam_rank_gap_min": min(swapped),
                                "beam_rank_gap_median": float(
                                    np.median(swapped))},
        "control_bf16": {
            "nospeech_logp_err": rms([ref.no_speech_logp(lo, vocab) - r
                                      for lo, r in zip(lg_low, ref_lp)]),
            "first_choice_gap": max(flat),
            "first_choice_differs": sum(g > 0 for g in flat) / len(flat)},
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    from drivers.asr_engine import effective
    from harness import spec

    entry = next(c for c in spec.load_bench()["configs"]
                 if c["name"] == a.config)
    cfg = effective(spec.load_json(spec.ROOT / entry["file"]), a.rehearse)
    for k, v in cfg["deployment"]["env"].items():
        os.environ[k] = str(v)
    if a.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        spec.compile_cache_dir()
    import jax

    if not a.rehearse:
        spec.keep_every_program(jax)
        if jax.devices()[0].platform != "tpu":
            sys.exit("control.py: no TPU (--rehearse for the CPU)")
    from drivers.asr_engine import apply_precision

    apply_precision(cfg["deployment"])
    env = cfg["deployment"]["env"]
    rows = 2 if a.rehearse else int(env["VLOG_ASR_BATCH_WINDOWS"])
    for seed in a.seeds:
        print(json.dumps(readings(cfg, seed, rows,
                                  int(env["VLOG_WHISPER_BEAM"]))), flush=True)


if __name__ == "__main__":
    main()
