"""Driver ``qwen3next_engine``: digests through the production entry on
Qwen3-Next-80B-A3B.

Everything of the served path is ``drivers/lm_engine.py``'s (the clients
over one shared cursor, the window between step boundaries, the audio
credit, the step summary, the capture's scopes, the comparison's
measures): the same ``digest_tokens`` against the same ``LmEngine``.
This file supplies what names the family: the weights
(``models/qwen3next_weights.py``), the plain reference
(``reference/qwen3next_ref.py``), the watched requests and, in
``extra``, the forms the DeltaNet and the attention took, the state
slots and the held share of the routing.

**correct**: the timed path's own logits at the prompt's last position
and at every output step of the first finished clip, talk and stream
among the requests that followed the clients' first ones
(:meth:`QwenRun.pick_watched`), prefill in chunks through the CHUNKWISE
DeltaNet and then decoding through the state slots in the RECURRENT
form, against the reference's full forward pass (the recurrence
position by position, no cache) over prompt plus served tokens, by
Trinity's measures (``lm_engine.compared_of``: ``logit_err``,
``beam_rank_gap`` with the router's near-ties under ``route_eps`` left
out and counted) and by ``median_logit_err`` (the largest, over the
kinds, of the median error of ALL of a kind's positions).
"""

from __future__ import annotations

import time

import numpy as np

from drivers.asr_engine import verdict
from drivers import lm_engine as base
from drivers.lm_engine import (Run, audio_credit, compared_of, log,
                               picked_items, scope_seconds, step_summary)


class QwenRun(Run):
    def pick_watched(self) -> set[int]:
        """Playlist places whose logits the run keeps: the first two of
        each class after the clients' first requests. They enter while
        the ramp runs (under the same load, through the same programs),
        and the streams among them end before the window closes."""
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

        from models.qwen3next_weights import make_params
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
    from reference import qwen3next_ref as ref

    req = item.request
    steps = sorted(req.logits)
    full = np.concatenate([item.ids, np.asarray(req.tokens[:-1], np.int32)])
    out = ref.forward(params, model, full,
                      [item.ids.size - 1 + s for s in steps],
                      prompt=item.ids.size,
                      chunk=int(model["deployment"]["chunk"])
                      if "deployment" in model else 2048, **how)
    return {"steps": steps, "tokens": int(full.size), **out}


def readings(item, rows: dict, logits_of, chk: dict, token_of=None) -> dict:
    """``lm_engine.readings`` (``flipped``: the positions of all whose
    error passes ``flip_err``) and beside them ``all_errs``, the error
    of EVERY position."""
    from reference.qwen3next_ref import logit_error

    got = base.readings(item, rows, logits_of,
                        {**chk, "logit_err": chk["flip_err"]}, token_of)
    got["all_errs"] = [logit_error(logits_of(s), rows["logits"][i])
                       for i, s in enumerate(rows["steps"])]
    return got


def compared(run: Run, by_kind: dict) -> dict:
    """``lm_engine.compared_of``'s numbers, each beside its limit, and
    ``median_logit_err`` after ``flipped_share``."""
    out = compared_of(run, by_kind)
    items = list(out.items())
    at = [k for k, _ in items].index("flipped_share") + 1
    mine = {"median_logit_err": {
        "value": max((float(np.median(g["all_errs"]))
                      for g in by_kind.values()), default=1e30),
        "limit": run.cfg["check"]["median_logit_err"]}}
    return dict(items[:at] + list(mine.items()) + items[at:])


def check(run: Run) -> dict:
    """Served logits against the plain reference (module docstring)."""
    by_kind, detail = {}, {}
    model = {**run.model, "deployment": run.dep}
    for kind, it in sorted(picked_items(run).items()):
        rows = reference_rows(it, run.params, model)
        log(f"reference done: {kind}, {rows['tokens']} tokens")
        got = readings(it, rows, it.request.logits.__getitem__,
                       run.cfg["check"])
        by_kind[kind] = got
        detail[kind] = {"index": it.index, "tokens": rows["tokens"],
                        "positions": got["n"], "ties": got["ties"],
                        "flipped": got["flipped"],
                        "median_err": float(np.median(got["all_errs"])),
                        "margin_and_err": got["kept"]}
    run.check_detail = detail
    return compared(run, by_kind)


def run(cell, opts, tracer) -> dict:
    from harness.spec import plugin

    r = QwenRun(cell, opts)
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
    forms: dict[str, int] = {}
    for rec in records:
        for key in ("attn_rows_form", "attn_chunk_form", "gdn_rows_form",
                    "gdn_chunk_form"):
            if rec.get(key):
                name = f"{key.removesuffix('_form')}.{rec[key]}"
                forms[name] = forms.get(name, 0) + 1
    held = [rec["held_choices"] for rec in records if rec.get("held_choices")]
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
            "forms": forms,
            "state_slots_max": max(rec.get("state_slots") or 0
                                   for rec in records),
            "held_choices": [sum(h for h, _ in held), sum(a for _, a in held)],
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
