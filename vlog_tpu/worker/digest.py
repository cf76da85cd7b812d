"""Digest job: captions -> a transcript model -> chapters and a summary.

Runs after a transcription job has written ``captions.vtt`` and only on
a worker whose ``VLOG_DIGEST_DIR`` names a model directory
(``lm/load.py``; its ``config.json`` says which of the families the
program builds it is, and nothing here depends on that). The job reads
the cues, builds ``instruction + transcript`` (one ``[HH:MM:SS] text``
line per cue, cut at the engine's context cap), asks the process's shared step engine (``lm/engine.py``)
for at most ``max_new`` tokens, and writes ``chapters.vtt`` and
``digest.json`` beside ``captions.vtt``.

:func:`digest_tokens` is the part between the tokenizer and the files:
token ids in, the finished request out. The job calls it, and so does
whoever wants to drive the served path with ids of its own.

Spans (under the daemon's ``worker.digest``): ``digest.job.prompt``
(read, build, tokenize), ``digest.job.served`` (submit to last token),
``digest.job.write``; their seconds land in ``stats_out`` as
``prompt_s``, ``served_s`` (with ``queue_s``: submit to first chunk, and
``first_token_s``) and ``write_s``.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass
from pathlib import Path

from vlog_tpu import config
from vlog_tpu.asr.vtt import Cue, format_vtt
from vlog_tpu.obs import trace

INSTRUCTION = (
    "You are given the transcript of a video, one line per caption with "
    "its start time. Write a chapter list, one chapter per line as "
    "[HH:MM:SS] title, then a line 'Summary:' with a summary of at most "
    "three sentences, then a line 'Tags:' with up to eight tags separated "
    "by commas.\n\nTranscript:\n")
MAX_NEW = 384
_CUE_RE = re.compile(
    r"(?:(\d+):)?(\d\d):(\d\d)\.(\d{3})\s+-->\s+(?:(\d+):)?(\d\d):(\d\d)"
    r"\.(\d{3})")
_CHAPTER_RE = re.compile(
    r"^\s*\[?(?:(\d{1,2}):)?(\d{1,2}):(\d\d)\]?\s*[-:]?\s*(\S.*)$")


class DigestUnavailable(RuntimeError):
    """No transcript model configured (``VLOG_DIGEST_DIR``), or no
    captions to digest."""


@dataclass
class DigestResult:
    model: str
    chapters_path: str
    digest_path: str
    chapters: int
    prompt_tokens: int
    output_tokens: int


def parse_vtt(text: str) -> list[Cue]:
    """The cues of a WebVTT file as ``format_vtt`` writes it."""
    cues: list[Cue] = []
    lines = text.splitlines()
    i = 0
    while i < len(lines):
        m = _CUE_RE.search(lines[i])
        i += 1
        if m is None:
            continue
        h0, m0, s0, ms0, h1, m1, s1, ms1 = (int(g or 0) for g in m.groups())
        body = []
        while i < len(lines) and lines[i].strip():
            body.append(lines[i].strip())
            i += 1
        text_ = " ".join(body).replace("&lt;", "<").replace(
            "&gt;", ">").replace("&amp;", "&")
        cues.append(Cue(h0 * 3600 + m0 * 60 + s0 + ms0 / 1000,
                        h1 * 3600 + m1 * 60 + s1 + ms1 / 1000, text_))
    return cues


def _clock(t: float) -> str:
    t = int(max(0.0, t))
    return f"{t // 3600:02d}:{t % 3600 // 60:02d}:{t % 60:02d}"


def build_prompt(tokenizer, cues: list[Cue], *, room: int) -> list[int]:
    """``instruction + transcript`` as ids, the transcript cut (whole
    lines, from the end) to ``room`` positions."""
    ids = list(tokenizer.encode(INSTRUCTION))
    for c in cues:
        line = tokenizer.encode(f"[{_clock(c.start_s)}] {c.text}\n")
        if len(ids) + len(line) > room:
            break
        ids.extend(line)
    return ids[:room]


def digest_tokens(engine, prompt_ids, *, max_new: int, job_key: str,
                  eos_id: int | None = None, capture: tuple[int, ...] = (),
                  stats_out: dict | None = None):
    """Token ids through the shared step engine: submit, wait for the
    last token, book the stages. Returns the finished request (its
    ``tokens``, and ``logits`` at the ``capture`` steps)."""
    if stats_out is None:
        stats_out = {}
    with trace.span("digest.job.served", prompt_tokens=len(prompt_ids),
                    max_new=max_new) as stage:
        req = engine.submit(prompt_ids, max_new=max_new, tag=job_key,
                            eos_id=eos_id, capture=capture)
        req.wait()
    s = req.stats
    stats_out.update({
        "served_s": stage.duration_s,
        "queue_s": s.get("t_first_chunk", s["t_submit"]) - s["t_submit"],
        "first_token_s": s.get("t_first_token", s["t_submit"])
        - s["t_submit"],
        "prefill_steps": s.get("prefill_steps", 0),
        "prompt_tokens": int(len(prompt_ids)),
        "output_tokens": len(req.tokens)})
    return req


def parse_digest(text: str, *, end_s: float) -> dict:
    """Chapters, summary and tags out of the model's answer. An answer
    with no readable chapter line gives one chapter over the whole
    recording, so that ``chapters.vtt`` is always a valid file."""
    chapters, summary, tags = [], [], []
    mode = "chapters"
    for line in text.splitlines():
        low = line.strip().lower()
        if low.startswith("summary:"):
            mode = "summary"
            line = line.split(":", 1)[1]
        elif low.startswith("tags:"):
            mode = "tags"
            line = line.split(":", 1)[1]
        if mode == "chapters":
            m = _CHAPTER_RE.match(line)
            if m:
                h, mi, s = (int(g or 0) for g in m.groups()[:3])
                at = h * 3600 + mi * 60 + s
                if at <= end_s and (not chapters
                                    or at > chapters[-1]["start_s"]):
                    chapters.append({"start_s": float(at),
                                     "title": m.group(4).strip()[:120]})
        elif mode == "summary" and line.strip():
            summary.append(line.strip())
        elif mode == "tags":
            tags += [t.strip()[:40] for t in line.split(",") if t.strip()]
    if not chapters:
        chapters = [{"start_s": 0.0, "title": "Chapter 1"}]
    return {"chapters": chapters, "summary": " ".join(summary)[:2000],
            "tags": tags[:8]}


def _write(path: Path, text: str) -> None:
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(text)
    tmp.rename(path)


def digest_video(out_dir: str | Path, *, scheduler=None,
                 job_key: str | None = None, max_new: int = MAX_NEW,
                 stats_out: dict | None = None) -> DigestResult:
    """Full digest job for one video (daemon handler entrypoint), on the
    process's shared engine over ``VLOG_DIGEST_DIR``'s model."""
    if stats_out is None:
        stats_out = {}
    out_dir = Path(out_dir)
    model_dir = config.DIGEST_DIR
    if not model_dir or not Path(model_dir).exists():
        raise DigestUnavailable(
            "no transcript model: set VLOG_DIGEST_DIR to a local model "
            "directory (config.json, model.safetensors, tokenizer.json)")
    from vlog_tpu.lm.engine import get_engine

    engine = get_engine(model_dir, scheduler=scheduler)
    vtt = out_dir / "captions.vtt"
    if not vtt.exists():
        raise DigestUnavailable(f"{vtt}: no captions to digest")
    tokenizer = engine.assets.tokenizer
    with trace.span("digest.job.prompt") as stage:
        cues = parse_vtt(vtt.read_text())
        room = engine.geo.context_cap - max_new
        ids = build_prompt(tokenizer, cues, room=room)
    stats_out["prompt_s"] = stage.duration_s
    stats_out["cues"] = len(cues)
    req = digest_tokens(engine, ids, max_new=max_new,
                        job_key=job_key or str(out_dir),
                        eos_id=engine.assets.eos_id, stats_out=stats_out)
    with trace.span("digest.job.write") as stage:
        out = [t for t in req.tokens if t != engine.assets.eos_id]
        end_s = cues[-1].end_s if cues else 0.0
        parsed = parse_digest(tokenizer.decode(out), end_s=end_s)
        starts = [c["start_s"] for c in parsed["chapters"]] + [
            max(end_s, parsed["chapters"][-1]["start_s"] + 1.0)]
        _write(out_dir / "chapters.vtt", format_vtt([
            Cue(a, b, c["title"]) for a, b, c
            in zip(starts, starts[1:], parsed["chapters"])]))
        _write(out_dir / "digest.json", json.dumps({
            "model": engine.assets.model_name, **parsed,
            "prompt_tokens": len(ids), "output_tokens": len(req.tokens)},
            indent=1))
    stats_out["write_s"] = stage.duration_s
    return DigestResult(
        model=engine.assets.model_name,
        chapters_path=str(out_dir / "chapters.vtt"),
        digest_path=str(out_dir / "digest.json"),
        chapters=len(parsed["chapters"]), prompt_tokens=len(ids),
        output_tokens=len(req.tokens))
