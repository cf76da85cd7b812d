"""Driver ``keye_engine``: digests through the production entry on
Keye-VL-2.0's language model.

Everything of the served path is ``drivers/lm_engine.py``'s (the clients
over one shared cursor, the window between step boundaries, the audio
credit, the step summary, the capture's scopes): the same
``digest_tokens`` against the same ``LmEngine``. This file supplies what
names the family: the weights (``models/keye_weights.py``), the plain
reference (``reference/keye_ref.py``) and, in ``correct``, the
selection's margin beside the router's.

**correct**: the timed path's own logits at the prompt's last position
and at every output step of the first finished clip, talk and stream
among the requests that followed the clients' first ones
(``KeyeRun.pick_watched`` says why not after the window opened),
against the reference's full forward pass over prompt plus served
tokens, by the median error of each kind's positions (:func:`compared`
says why not by the worst). The positions whose own selection margin
(the smallest, over the layers, of the 2,048th index score over the
2,049th) is under ``select_eps`` are counted (``select_tie_share``).
The pool's bound is part of the run: the requests that waited for pages
are in ``extra.engine_stats.pool_wait``.
"""

from __future__ import annotations

import time

import numpy as np

from drivers import lm_engine as base
from drivers.asr_engine import verdict
from drivers.lm_engine import (Run, audio_credit, compared_of, log,
                               picked_items, scope_seconds, step_summary)
from reference.keye_ref import logit_error


class KeyeRun(Run):
    def pick_watched(self) -> set[int]:
        """Playlist places whose logits the run keeps: the first two of
        each class after the clients' first requests. Not, as on
        Trinity's cell, after the window can have opened: a stream's 768
        output tokens take 768 steps after its 10 to 18 chunks and a
        window holds some 750 steps, so a stream that enters after the
        window opens never ends inside it. These enter while the ramp
        runs (under the same load, through the same programs) and the
        stream among them ends inside the window."""
        playlist = self.plan["playlist"]
        first = self.plan["clients"]
        out: set[int] = set()
        for kind in {p["kind"] for p in playlist}:
            out.update([i for i in range(first, first + 2 * len(playlist))
                        if playlist[i % len(playlist)]["kind"] == kind][:2])
        return out

    def build(self):
        self.parts["before_build_s"] = time.perf_counter() - self.opts.t_start
        import jax

        from models.keye_weights import make_params
        from vlog_tpu.lm.engine import LmEngine
        from vlog_tpu.lm.load import LmAssets
        from vlog_tpu.lm.model import Geometry, LmConfig
        from vlog_tpu.worker import digest

        self.jax, self.digest = jax, digest
        # first, so that a program without the family fails at once
        config = LmConfig.from_hf(self.model)
        t0 = time.monotonic()
        self.params = make_params(self.model, self.opts.seed)
        jax.block_until_ready(self.params)
        self.parts["weights_s"] = time.monotonic() - t0
        log("weights made")
        geo = Geometry(**{k: int(self.dep[k]) for k in (
            "rows", "chunk", "page", "context_cap", "kv_block_pages",
            "window_pages", "full_pages")})
        assets = LmAssets(cfg=config, params=self.params, tokenizer=None,
                          model_name=self.cell.config_name)
        self.engine = LmEngine(assets, geometry=geo)
        t0 = time.monotonic()
        self.engine.prepare()
        self.parts["prepare_s"] = time.monotonic() - t0
        log("engine prepared")
        rng = np.random.default_rng([int(self.opts.seed), 31])
        self.instruction = rng.integers(
            0, self.model["vocab_size"],
            int(self.traffic["params"]["instruction_tokens"]),
            dtype=np.int32)


def reference_rows(item, params, model: dict, **how) -> dict:
    """The plain reference's full forward pass over the request's prompt
    plus served tokens, at the positions whose logits the run kept."""
    from reference import keye_ref as ref

    req = item.request
    steps = sorted(req.logits)
    full = np.concatenate([item.ids, np.asarray(req.tokens[:-1], np.int32)])
    out = ref.forward(params, model, full,
                      [item.ids.size - 1 + s for s in steps], **how)
    return {"steps": steps, "tokens": int(full.size), **out}


def readings(item, rows: dict, logits_of, chk: dict, token_of=None) -> dict:
    """``lm_engine.readings`` (rank gaps of the positions whose router
    margin stands; ``flipped``, the positions of all whose error passes
    ``flip_err``), and beside them ``all_errs``, the error of EVERY
    position, ``select_ties``, the positions whose selection margin is
    under ``select_eps`` (counted, never left out: what moves a long
    request's logits is the keys that positions BEFORE it chose, and no
    margin of its own says that), and ``chooses``: whether any position
    had more keys than the selection keeps."""
    got = base.readings(item, rows, logits_of,
                        {**chk, "logit_err": chk["flip_err"]}, token_of)
    got["all_errs"] = [logit_error(logits_of(s), rows["logits"][i])
                       for i, s in enumerate(rows["steps"])]
    got["select_ties"] = int(np.sum(
        np.asarray(rows["select_gap"]) < chk["select_eps"]))
    got["chooses"] = bool(np.isfinite(rows["select_gap"]).any())
    return got


def _median_err(kinds) -> float:
    """The largest, over the kinds, of the median error of a kind's
    positions."""
    return max((float(np.median(g["all_errs"])) for g in kinds),
               default=1e30)


def compared(run: Run, by_kind: dict) -> dict:
    """The compared numbers, each beside its limit: ``lm_engine``'s list
    with the errors read by their MEDIAN over a kind's positions.

    With these weights (softmax routing over 128 experts whose 8th and
    9th probability lie 1e-4 apart) an expert chosen otherwise moves a
    logit row by up to 0.17 of its spread, and ONE key chosen otherwise,
    in any layer of any earlier position, flips experts downstream: the
    reference against itself with one key swapped reads 0.37 to 0.47 at
    its worst position and 0.13 at the median (``control_keye.py``). So
    the worst position says how the near-ties fell, and the median over
    96, 256 or 768 positions says whether the program is right.
    ``logit_err`` is the median of the kinds that choose nothing (a
    clip attends every key: only the precision shows, and the reference
    wholly in bfloat16 reads twice the program); ``long_logit_err`` that
    of the kinds that choose (a talk, a stream: the selection shows, and
    a reference that attends every key, or the newest, reads four and
    twenty times the program). ``positions_compared``,
    ``beam_rank_gap``, ``route_tie_share`` and ``flipped_share`` are
    ``lm_engine.compared_of``'s over the kinds that choose nothing."""
    chk = run.cfg["check"]
    exact = {k: g for k, g in by_kind.items() if not g["chooses"]}
    long_ = [g for g in by_kind.values() if g["chooses"]]
    out = compared_of(run, exact)
    out["kinds_compared"] = {"value": len(by_kind),
                             "limit": f">={chk.get('min_kinds', 1)}"}
    out["logit_err"] = {"value": _median_err(exact.values()),
                        "limit": chk["logit_err"]}
    n = sum(g["n"] for g in long_)
    extra = {
        "long_kinds_compared": {"value": len(long_),
                                "limit": f">={chk['min_long_kinds']}"},
        "long_logit_err": {"value": _median_err(long_),
                           "limit": chk["long_logit_err"]},
        "select_tie_share": {
            "value": sum(g["select_ties"] for g in long_) / n if n else 1.0,
            "limit": chk["select_tie_share"]}}
    # after the shares, before the counts that close the list
    items = list(out.items())
    at = [k for k, _ in items].index("flipped_share") + 1
    return dict(items[:at] + list(extra.items()) + items[at:])


def check(run: Run) -> dict:
    """Served logits against the plain reference (module docstring)."""
    by_kind, detail = {}, {}
    for kind, it in sorted(picked_items(run).items()):
        rows = reference_rows(it, run.params, run.model)
        log(f"reference done: {kind}, {rows['tokens']} tokens")
        got = readings(it, rows, it.request.logits.__getitem__,
                       run.cfg["check"])
        by_kind[kind] = got
        finite = rows["select_gap"][np.isfinite(rows["select_gap"])]
        detail[kind] = {"index": it.index, "tokens": rows["tokens"],
                        "positions": got["n"], "ties": got["ties"],
                        "select_ties": got["select_ties"],
                        "flipped": got["flipped"],
                        "smallest_select_gap": float(finite.min())
                        if finite.size else None,
                        # per position: the router's margin, the
                        # selection's (null: every key attended), the error
                        "margins_and_err": [
                            [round(float(r), 6),
                             round(float(g), 6) if np.isfinite(g) else None,
                             round(e, 5)]
                            for r, g, e in zip(rows["route_gap"],
                                               rows["select_gap"],
                                               got["all_errs"])]}
    run.check_detail = detail
    return compared(run, by_kind)


def run(cell, opts, tracer) -> dict:
    from harness.spec import plugin

    r = KeyeRun(cell, opts)
    gen = plugin("generators", r.traffic["generator"])
    r.plan = gen.generate(r.traffic["params"], seed=opts.seed,
                          seconds=opts.seconds)
    r.build()
    window, records = r.serve(tracer)
    log(f"window closed at step {window['close_step']}")
    r.window_records = records
    scope_s = scope_seconds(r, tracer)
    log("capture read")
    tracer.stop_now()
    mem = [d.memory_stats() or {} for d in r.jax.local_devices()[:cell.chips]]
    fullest = max(mem, key=lambda m: m.get("peak_bytes_in_use", 0)
                  + m.get("peak_bytes_reserved", 0))
    peak = (fullest.get("peak_bytes_in_use", 0)
            + fullest.get("peak_bytes_reserved", 0))
    engine_stats = r.engine.stats()
    r.stop_traffic()
    log("engine closed")
    r.free_program()
    log("program freed")

    t0 = time.monotonic()
    compared_ = check(r)
    check_s = time.monotonic() - t0

    by_tag = {it.tag: it.spec for it in r.items}
    span_s = window["t_end"] - window["t0"]
    audio = audio_credit(records, by_tag)
    items = r.items
    trace_steps = []
    if r.trace_span is not None:
        a, b = r.trace_span
        trace_steps = [rec for rec in records if a <= rec["t_ready"] <= b]
    waited = [rec["pool_wait_rows"] for rec in records]
    return {
        "correct": verdict(compared_), "compared": compared_,
        "attempted": sum(1 for it in items if it.status != "cut"),
        "failed": sum(1 for it in items if it.status == "failed"),
        "end_to_end": {"audio_s_per_s": audio / span_s,
                       "setup_s": r.parts["setup_s"]},
        "memory_peak_bytes": int(peak),
        "extra": {
            "window_s": span_s, "steps": len(records),
            "step_summary": step_summary(records, span_s),
            "audio_s": audio,
            "tokens": {"prefill": sum(x["prefill_tokens"] for x in records),
                       "decode": sum(x["decode_rows"] for x in records)},
            "requests": {s: sum(1 for it in items if it.status == s)
                         for s in ("ok", "cut", "failed")},
            "open_step": window["open_step"],
            "close_step": window["close_step"],
            "engine_stats": engine_stats,
            "pool": {"steps_waiting": sum(1 for w in waited if w),
                     "rows_waiting": sum(waited),
                     "pages_in_use_max": max(
                         rec["pages_in_use"]["full"] for rec in records)},
            "memory_parts": {k: fullest.get(k) for k in (
                "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")},
            "setup_parts_s": r.parts, "check_s": check_s,
            "check_detail": r.check_detail,
            "by_scope_s": scope_s,
            "trace_steps": len(trace_steps),
            "errors": sorted({it.error for it in items
                              if it.status == "failed"})[:5],
        },
        "layer_ctx": {"step_log": records, "trace_steps": trace_steps,
                      # the two list-free ASR metrics, as lm_engine
                      # reports them: a step is this engine's tick
                      "batch_log": [{"n": x["decode_rows"],
                                     "rows": int(r.dep["rows"]),
                                     "elapsed_s": x["step_s"]}
                                    for x in records],
                      "scope_s": scope_s, "model": r.model,
                      "window": window},
    }
