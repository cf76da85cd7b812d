#!/usr/bin/env python3
"""chip_smoke.py — does upload -> ladder -> captions still start on the chip?

One process, the only one that touches JAX, drives the system's main
path once through the entry points a deployment uses:

    claims.enqueue_job -> WorkerDaemon.run() ->
        transcribe_video -> AsrEngine (Whisper at the widths of `small`)
                         -> captions.vtt
        process_video    -> JaxBackend.run -> chain ladder program
                         -> host CABAC -> CMAF tree
        generate_sprites (the job the transcode enqueues)

and then checks what came out by the repo's own means: the integrity
manifest verifies, the master and media playlists validate, the first
frames of the top and bottom rung decode (codecs/h264/decoder.py) to a
PSNR-Y against the resized source above a floor, ``captions.vtt``
parses, and the engine decoded exactly the windows the audio holds.

Contract (the driver runs this after every PR):

- exits non-zero and prints no result unless
  ``jax.devices()[0].platform == "tpu"`` — nothing is ever measured on,
  or reported from, XLA:CPU;
- needs no network and no git: inputs come from a seed, the native
  coders are built from the committed sources;
- stops what it starts, deletes only the work directory it created;
- on success the LAST line of stdout is
  ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Everything printed before that line is a bring-up observation (compile
seconds, per-job wall, stage seconds, peak HBM), not a benchmark result.

With more than one chip visible the same run drives the ladder's
``Nx1`` grid (one chain per device) and fails if any device stayed
idle; with ``VLOG_MESH_SLOTS`` > 1 it additionally requires the lone
transcode to have leased the whole mesh and the scheduler to be idle
and unquarantined after the drain.
"""

from __future__ import annotations

import asyncio
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

# --------------------------------------------------------------------------
# What the smoke transcodes.
#
# 1080p, not 2160p: on a TPU v5 lite (PR 21, jax 0.9.0) ONE 20-frame chain
# of the 2160p rung alone took 454 s to run (36 s to compile) — the
# P-frame path is bound by per-pixel gathers — so a six-rung 2160p source
# cannot finish one dispatch inside this script's 1200 s limit, compile or
# no compile. The four-rung ladder of a 1080p source ran at 140 s per
# chain (92 s compile, 58 s lowering). Decided here, in code; there is no
# step-down at run time. ROADMAP Speed item 10 carries the numbers.
# --------------------------------------------------------------------------
SRC_H, SRC_W, FPS = 1080, 1920, 30
CHAINS_ONE_CHIP = 3          # dispatches on one chip; N chips get N chains
AUDIO_S = 65.0               # three 30 s Whisper windows at 5 s overlap
SEED = 21

# The published widths of Whisper `small`, the model VLOG_WHISPER_MODEL
# defaults to. Weights are random (seeded); depth is the published 12+12.
WHISPER_SMALL = dict(
    d_model=768, encoder_layers=12, decoder_layers=12,
    encoder_attention_heads=12, decoder_attention_heads=12,
    encoder_ffn_dim=3072, decoder_ffn_dim=3072, num_mel_bins=80,
    vocab_size=51865, max_source_positions=1500, max_target_positions=448)

# Decoded PSNR-Y floor against the (resized) source. CPU runs of this
# seeded generator decode to 31.2 / 34.0 dB at the tiny size
# (tests/test_chip_smoke.py: 144p source, rungs 144p/72p) and 28.7 dB
# for a 360p rung at its ladder QP — the +-20 uniform luma noise is most
# of the error. Broken prediction or a drifting reference lands near 10.
PSNR_FLOOR_DB = 20.0
DECODE_FRAMES = 3            # one I and two P frames of the first segment

# Whole-run wall limit the driver enforces, and the bound on the wait for
# the queue to drain inside it (the rest is input generation and checks).
WAIT_S = 1000.0

_LANGS = (
    "en zh de es ru ko fr ja pt tr pl ca nl ar sv it id hi fi vi he uk el "
    "ms cs ro da hu ta no th ur hr bg lt la mi ml cy sk te fa lv bn sr az "
    "sl kn et mk br eu is hy ne mn bs kk sq sw gl mr pa si km sn yo so af "
    "oc ka be tg sd gu am yi lo uz fo ht ps tk nn mt sa lb my bo tl mg as "
    "tt haw ln ha ba jw su").split()


def _say(*parts) -> None:
    print("[chip_smoke]", *parts, flush=True)


class SmokeFailure(RuntimeError):
    """A phase of the smoke did not hold."""


# --------------------------------------------------------------------------
# Inputs, all from a seed
# --------------------------------------------------------------------------

def source_frames(*, height: int, width: int, n_frames: int, seed: int):
    """Structured frames (bench.py's generator): 8-px gradient blocks
    shifting one pixel per frame — real motion for the chain's search —
    plus uniform noise for real residual; static random chroma."""
    import numpy as np

    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:height, 0:width]
    base = ((yy // 8 + xx // 8) % 256).astype(np.int16)
    uv = rng.integers(0, 256, (height // 2, width // 2)).astype(np.uint8)
    for i in range(n_frames):
        y = np.clip(np.roll(base, i, axis=1)
                    + rng.integers(-20, 20, base.shape),
                    0, 255).astype(np.uint8)
        yield y, uv, uv


def write_tone_wav(path: Path, *, seconds: float, seed: int) -> None:
    """A 220 Hz tone plus noise at 16 kHz: energy the VAD passes in
    every window (what the engine tests drive)."""
    import numpy as np

    from vlog_tpu.media.audio import AudioData, write_wav

    sr = 16000
    rng = np.random.default_rng(seed)
    t = np.arange(int(seconds * sr)) / sr
    pcm = 0.25 * np.sin(2 * np.pi * 220.0 * t) \
        + 0.01 * rng.standard_normal(t.shape)
    write_wav(path, AudioData(pcm=pcm[None].astype(np.float64),
                              sample_rate=sr))


def build_whisper_checkpoint(model_dir: Path, *, seed: int) -> None:
    """A Whisper checkpoint at the widths of `small` in the HF layout
    ``asr/load.py::load_whisper`` reads: ``config.json``,
    ``model.safetensors`` (seeded random weights) and an offline
    byte-level tokenizer whose ids sit where the published multilingual
    vocabulary puts them (50,257 text ids, then <|endoftext|>, <|sot|>,
    99 languages, task and control tokens, 1,501 timestamps = 51,865)."""
    import transformers
    from safetensors.numpy import save_file
    from transformers.models.gpt2.tokenization_gpt2 import bytes_to_unicode

    from vlog_tpu.asr.model import WhisperConfig, random_state_dict

    model_dir.mkdir(parents=True, exist_ok=True)
    alphabet = [ch for _, ch in sorted(bytes_to_unicode().items())]
    vocab = {ch: i for i, ch in enumerate(alphabet)}
    # fill the text range with distinct two-character tokens: every id a
    # random-weight decoder can emit must decode to something
    a = 0
    while len(vocab) < 50257:
        tok = alphabet[a // 256 % 256] + alphabet[a % 256] + alphabet[a // 65536]
        vocab.setdefault(tok, len(vocab))
        a += 1
    (model_dir / "vocab.json").write_text(json.dumps(vocab))
    (model_dir / "merges.txt").write_text("#version: 0.2\n")
    tok = transformers.WhisperTokenizer(
        str(model_dir / "vocab.json"), str(model_dir / "merges.txt"),
        unk_token="<|endoftext|>", bos_token="<|endoftext|>",
        eos_token="<|endoftext|>")
    specials = (["<|endoftext|>", "<|startoftranscript|>"]
                + [f"<|{code}|>" for code in _LANGS]
                + ["<|translate|>", "<|transcribe|>", "<|startoflm|>",
                   "<|startofprev|>", "<|nospeech|>", "<|notimestamps|>"])
    tok.add_special_tokens({"additional_special_tokens": specials})
    tok.save_pretrained(str(model_dir))
    ids = {s: tok.convert_tokens_to_ids(s) for s in specials}
    if ids["<|endoftext|>"] != 50257 or ids["<|notimestamps|>"] != 50363:
        raise SmokeFailure(f"tokenizer ids off the published layout: "
                           f"eot={ids['<|endoftext|>']} "
                           f"notimestamps={ids['<|notimestamps|>']}")
    hf_cfg = dict(
        WHISPER_SMALL, model_type="whisper",
        decoder_start_token_id=ids["<|startoftranscript|>"],
        eos_token_id=ids["<|endoftext|>"], pad_token_id=ids["<|endoftext|>"],
        bos_token_id=ids["<|endoftext|>"],
        suppress_tokens=[], begin_suppress_tokens=[])
    (model_dir / "config.json").write_text(json.dumps(hf_cfg))
    save_file(random_state_dict(WhisperConfig.from_hf(hf_cfg), seed),
              str(model_dir / "model.safetensors"))


# --------------------------------------------------------------------------
# Checks
# --------------------------------------------------------------------------

_CUE_TIME = re.compile(
    r"^(\d{2,}):(\d{2}):(\d{2})\.(\d{3}) --> (\d{2,}):(\d{2}):(\d{2})\.(\d{3})")


def parse_vtt(text: str) -> list[tuple[float, float, str]]:
    """Minimal WebVTT reader: header, then ``start --> end`` cues whose
    times parse and run forward. Raises SmokeFailure on anything else."""
    blocks = [b for b in text.replace("\r\n", "\n").split("\n\n") if b.strip()]
    if not blocks or not blocks[0].startswith("WEBVTT"):
        raise SmokeFailure("captions.vtt: missing WEBVTT header")
    cues = []
    for block in blocks[1:]:
        lines = block.strip().split("\n")
        idx = 0 if _CUE_TIME.match(lines[0]) else 1      # optional cue id
        m = _CUE_TIME.match(lines[idx]) if idx < len(lines) else None
        if m is None:
            raise SmokeFailure(f"captions.vtt: bad cue block {block[:80]!r}")
        g = [int(x) for x in m.groups()]
        start = g[0] * 3600 + g[1] * 60 + g[2] + g[3] / 1000
        end = g[4] * 3600 + g[5] * 60 + g[6] + g[7] / 1000
        if end < start:
            raise SmokeFailure(f"captions.vtt: cue runs backwards: "
                               f"{lines[idx]!r}")
        cues.append((start, end, "\n".join(lines[idx + 1:])))
    return cues


def _segment_samples(rung_dir: Path) -> tuple[bytes, list[bytes]]:
    """(avcC record, AVCC samples of segment 1) from a CMAF rung dir."""
    from vlog_tpu.media.boxes import parse_box_tree

    init = (rung_dir / "init.mp4").read_bytes()
    idx = init.find(b"avcC")
    if idx < 4:
        raise SmokeFailure(f"{rung_dir}: init.mp4 carries no avcC")
    size = int.from_bytes(init[idx - 4:idx], "big")
    avcc = init[idx + 4:idx - 4 + size]
    seg_path = rung_dir / "segment_00001.m4s"
    seg = seg_path.read_bytes()
    with open(seg_path, "rb") as fp:
        tree = parse_box_tree(fp)
    mdat = next(b for b in tree if b.type == "mdat")
    payload = seg[mdat.offset + 8:mdat.offset + mdat.size]
    trun = next(b for b in tree if b.type == "moof").find("traf", "trun")
    n = int.from_bytes(trun.payload[4:8], "big")
    sizes = [int.from_bytes(trun.payload[16 + 16 * k:20 + 16 * k], "big")
             for k in range(n)]
    samples, off = [], 0
    for sz in sizes:
        samples.append(payload[off:off + sz])
        off += sz
    return avcc, samples


def decoded_psnr_y(rung_dir: Path, source: Path, n_frames: int) -> float:
    """Decode the first ``n_frames`` of the rung's first segment with the
    in-repo decoder; mean PSNR-Y against the source resized to the
    rung's geometry by the ladder's own resize."""
    import numpy as np

    from vlog_tpu.codecs.h264.decoder import H264Decoder
    from vlog_tpu.media.y4m import Y4mReader
    from vlog_tpu.ops.resize import resize_yuv420

    avcc, samples = _segment_samples(rung_dir)
    dec = H264Decoder(avcc_config=avcc)
    frames = [dec.decode_sample(s) for s in samples[:n_frames]]
    if len(frames) < n_frames or any(f is None for f in frames):
        raise SmokeFailure(f"{rung_dir.name}: first segment holds fewer "
                           f"than {n_frames} decodable frames")
    h, w = frames[0].y.shape
    with Y4mReader(source) as rd:
        src = [rd.read_frame(i) for i in range(n_frames)]
    psnrs = []
    for got, (sy, su, sv) in zip(frames, src):
        if sy.shape != (h, w):
            ry, _, _ = resize_yuv420(sy[None], su[None], sv[None], h, w)
            sy = np.asarray(ry[0])
        mse = float(np.mean((got.y.astype(np.float64) - sy) ** 2))
        psnrs.append(99.0 if mse < 1e-9 else 10 * np.log10(255 ** 2 / mse))
    return float(np.mean(psnrs))


def _num_allocs(devices) -> dict[str, int | None]:
    """Allocations each device has served so far (None where the
    runtime keeps no stats: XLA:CPU)."""
    return {str(d.id): (d.memory_stats() or {}).get("num_allocs")
            for d in devices}


def _resize_planes(rung_names: list[str], src_h: int) -> dict[str, str]:
    """The resize plane each rung's program compiled with."""
    from vlog_tpu import config
    from vlog_tpu.ops.pallas_ladder import use_pallas

    plane = "fused" if use_pallas() else "xla"
    return {name: ("identity" if config.LADDER_BY_NAME[name].height >= src_h
                   else plane) for name in rung_names}


def mosaic_verdict() -> str:
    """Offer the fused Pallas plane to Mosaic for one shape it claims (a
    720p source's 360p luma plane) and say what happened. With
    ``VLOG_PALLAS=1`` this same trace is what a ladder program hits, so
    a refusal here is the error such a run raises."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vlog_tpu.ops.pallas_ladder import fused_resize_plane
    from vlog_tpu.ops.resize import apply_resize_matrices, resample_matrix

    x = jnp.asarray(np.random.default_rng(SEED).integers(
        0, 256, (1, 720, 1280), dtype=np.uint8))
    a_h = jnp.asarray(resample_matrix(720, 360))
    a_w = jnp.asarray(resample_matrix(1280, 640))
    try:
        got = jax.block_until_ready(jax.jit(fused_resize_plane)(x, a_h, a_w))
    except Exception as exc:  # noqa: BLE001 — the refusal IS the finding
        return f"refused ({type(exc).__name__}: {str(exc)[:120]})"
    same = bool(jnp.array_equal(got, apply_resize_matrices(x, a_h, a_w)))
    return f"accepted, bytes {'equal' if same else 'DIFFER from'} the XLA path"


# --------------------------------------------------------------------------
# The drive
# --------------------------------------------------------------------------

async def _drain(db, daemon, devices, *, wav: Path, y4m: Path,
                 wait_s: float) -> dict:
    """Enqueue, run the real daemon loop, stop it when the queue is dry.

    The transcription job goes in first and the transcode follows the
    moment it completes: the cheap model fails first if it is going to,
    and under the mesh scheduler the transcode is then the LONE device
    job, which must lease the whole mesh (the work-conserving rule)."""
    from vlog_tpu.enums import JobKind
    from vlog_tpu.jobs import claims, videos as vids

    audio_video = await vids.create_video(db, "Smoke captions",
                                          source_path=str(wav))
    await db.execute("UPDATE videos SET duration_s=:d WHERE id=:id",
                     {"d": AUDIO_S, "id": audio_video["id"]})
    asr_job = await claims.enqueue_job(db, audio_video["id"],
                                       JobKind.TRANSCRIPTION)
    ladder_video = await vids.create_video(db, "Smoke ladder",
                                           source_path=str(y4m))
    out: dict = {"audio_video": audio_video, "ladder_video": ladder_video,
                 "asr_job": asr_job, "ladder_job": None, "failures": [],
                 "timed_out": False}

    async def watcher() -> None:
        deadline = time.monotonic() + wait_s
        while True:
            await asyncio.sleep(0.5)
            out["failures"] = await db.fetch_all(
                "SELECT job_id, attempt, failure_class, error "
                "FROM job_failures ORDER BY id")
            if out["failures"]:
                break
            jobs = await db.fetch_all(
                "SELECT id, kind, completed_at FROM jobs")
            done = {j["id"] for j in jobs if j["completed_at"] is not None}
            if out["ladder_job"] is None and asr_job in done:
                out["allocs_before_ladder"] = _num_allocs(devices)
                out["ladder_job"] = await claims.enqueue_job(
                    db, ladder_video["id"], JobKind.TRANSCODE)
                continue
            # the transcode enqueues its sprite job before it completes
            if out["ladder_job"] in done and len(done) == len(jobs):
                break
            if time.monotonic() > deadline:
                out["timed_out"] = True
                break
        daemon.request_stop()

    task = asyncio.create_task(watcher(), name="vlog-smoke-watcher")
    try:
        await daemon.run()
    finally:
        await task
    out["jobs"] = await db.fetch_all(
        "SELECT id, kind, attempt, completed_at, failed_at, error FROM jobs")
    out["spans"] = await db.fetch_all(
        "SELECT job_id, name, duration_s, status, attributes FROM job_spans "
        "ORDER BY id")
    return out


def run_smoke(work: Path, *, require_platform: str | None = "tpu",
              src_h: int = SRC_H, src_w: int = SRC_W, fps: int = FPS,
              chains: int | None = None, whisper_dir: Path | None = None,
              wait_s: float = WAIT_S, psnr_floor: float = PSNR_FLOOR_DB,
              seed: int = SEED) -> dict:
    """Drive the main path once inside ``work`` (a fresh directory the
    caller owns) and return the observations; raises SmokeFailure when
    any phase did not hold. ``require_platform`` is the platform the
    first JAX device must report (None = any; the tier-1 test passes
    ``"cpu"`` with a tiny source and the conftest tiny Whisper)."""
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if require_platform is not None and dev["platform"] != require_platform:
        raise SmokeFailure(f"needs platform {require_platform!r}, JAX found "
                           f"{dev['platform']!r}")

    from vlog_tpu import config, native
    from vlog_tpu.asr.engine import peek_engine, reset_engine
    from vlog_tpu.backends import select_backend
    from vlog_tpu.db import Database, create_all
    from vlog_tpu.media import hls
    from vlog_tpu.media.y4m import write_y4m
    from vlog_tpu.parallel.compile_cache import (compile_seconds,
                                                 ensure_compile_cache)
    from vlog_tpu.storage import integrity
    from vlog_tpu.worker.daemon import WorkerDaemon

    cache_dir = ensure_compile_cache()
    _say(f"platform={dev['platform']} device_kind={dev['kind']!r} "
         f"devices={dev['count']} jax={jax.__version__} "
         f"compile_cache={cache_dir}")
    obs: dict = {"device": dev, "compile_cache_dir": cache_dir}

    # -- native coders, from the committed sources (content-hash stamp)
    t0 = time.monotonic()
    native.require_lib()
    obs["entropy_native"] = native.get_lib() is not None
    _say(f"native coders ready in {time.monotonic() - t0:.1f}s "
         f"(native={obs['entropy_native']})")

    # -- inputs
    backend = select_backend()
    t0 = time.monotonic()
    n_chains = chains or max(CHAINS_ONE_CHIP, dev["count"])
    video_dir = work / "videos"
    y4m, wav = work / "source.y4m", work / "speech.wav"
    from vlog_tpu.media.probe import VideoInfo

    # whole chains: the backend's own plan says how long one is here
    gop_len = backend.plan(VideoInfo(
        container="y4m", path=str(y4m), duration_s=0.0, width=src_w,
        height=src_h, fps=float(fps), frame_count=0, video_codec="raw",
        audio_codec=None, size_bytes=0), None, video_dir).gop_len
    n_frames = n_chains * gop_len
    write_y4m(y4m, source_frames(height=src_h, width=src_w,
                                 n_frames=n_frames, seed=seed), fps_num=fps)
    write_tone_wav(wav, seconds=AUDIO_S, seed=seed)
    if whisper_dir is None:
        whisper_dir = work / "whisper-small-random"
        build_whisper_checkpoint(whisper_dir, seed=seed)
    rungs = [r.name for r in config.ladder_for_source(src_h)]
    _say(f"inputs in {time.monotonic() - t0:.1f}s: {src_w}x{src_h}@{fps} "
         f"{n_frames} frames ({n_chains} chains of {gop_len}), rungs={rungs}, "
         f"{AUDIO_S:.0f}s wav, whisper={whisper_dir.name}")
    if integrity.under_pressure(work):
        raise SmokeFailure(
            f"{work}: below the VLOG_MIN_FREE_DISK_GB floor "
            f"({integrity.free_bytes(work) >> 30} GiB free); the daemon "
            "would never claim")

    # -- queue + daemon (default configuration)
    sched = None
    if config.MESH_SLOTS > 1:
        from vlog_tpu.parallel.scheduler import get_scheduler

        sched = get_scheduler()
    reset_engine()

    async def drive() -> dict:
        db = Database(f"sqlite:///{work / 'smoke.db'}")
        await db.connect()
        try:
            await create_all(db)
            daemon = WorkerDaemon(
                db, name="chip-smoke", backend=backend, video_dir=video_dir,
                scheduler=sched, transcription_model_dir=str(whisper_dir),
                progress_min_interval_s=0.5)
            return await _drain(db, daemon, devices, wav=wav, y4m=y4m,
                                wait_s=wait_s)
        finally:
            await db.disconnect()

    t_drive = time.monotonic()
    res = asyncio.run(drive())
    obs["drain_wall_s"] = round(time.monotonic() - t_drive, 1)
    obs["backend_compile_s"] = round(compile_seconds(), 1)
    if res["failures"] or res["timed_out"]:
        for row in res["failures"]:
            _say("job_failures:", json.dumps(dict(row), default=str))
        raise SmokeFailure(
            "the queue did not drain: "
            + ("a job failed" if res["failures"]
               else f"no drain within {wait_s:.0f}s")
            + f"; jobs={[dict(j) for j in res['jobs']]}")

    # -- observations from the job traces
    spans = [dict(s, attrs=json.loads(s["attributes"] or "{}"))
             for s in res["spans"]]
    kinds = {j["id"]: j["kind"] for j in res["jobs"]}
    obs["job_wall_s"] = {
        kinds[s["job_id"]]: round(s["duration_s"] or 0.0, 1)
        for s in spans if s["name"] == "worker.attempt"}
    tsp = next((s for s in spans if s["name"] == "worker.transcode"), None)
    if tsp is None:
        raise SmokeFailure("no worker.transcode span was recorded")
    obs["stage_s"] = {
        **{s["name"][6:] + "_s": s["duration_s"] for s in spans
           if s["name"].startswith("stage.")},
        **{k: v for k, v in tsp["attrs"].items()
           if not k.startswith("mesh.") and k != "rungs"}}
    obs["mesh"] = {k: v for k, v in tsp["attrs"].items()
                   if k.startswith("mesh.")}
    obs["resize_plane"] = _resize_planes(rungs, src_h)
    obs["peak_bytes_in_use"] = {
        str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in devices}
    # what the transcode (and its sprite job) allocated, device by
    # device: the captions ran first, so a peak alone cannot say where
    # the ladder landed
    before, after = res["allocs_before_ladder"], _num_allocs(devices)
    obs["ladder_allocs"] = {k: (None if after[k] is None
                                else after[k] - before[k]) for k in after}
    _say(f"drained in {obs['drain_wall_s']}s; backend compile "
         f"{obs['backend_compile_s']}s; per-job wall {obs['job_wall_s']}")
    _say(f"stage_s {json.dumps(obs['stage_s'])}")
    _say(f"mesh {obs['mesh']} resize_plane {obs['resize_plane']} "
         f"entropy_native={obs['entropy_native']}")
    _say(f"peak_bytes_in_use per device {obs['peak_bytes_in_use']}; "
         f"allocations during the transcode {obs['ladder_allocs']}")

    # -- multi-chip: nobody idle, and the grid is the one asked for
    if dev["count"] > 1:
        want = f"{dev['count']}x1"
        if obs["mesh"].get("mesh.shape") != want:
            raise SmokeFailure(f"grid {obs['mesh'].get('mesh.shape')!r}, "
                               f"expected {want!r} (VLOG_TPU_MESH=data:-1)")
        if dev["platform"] != "cpu":         # XLA:CPU reports no stats
            idle = [k for k in obs["peak_bytes_in_use"]
                    if not obs["peak_bytes_in_use"][k]
                    or not obs["ladder_allocs"][k]]
            if idle:
                raise SmokeFailure(
                    f"devices {idle} report zero peak bytes or served no "
                    "allocation during the transcode: the ladder landed "
                    "elsewhere")
    if sched is not None:
        snap = sched.snapshot()
        obs["scheduler"] = snap
        _say(f"scheduler after drain {snap}")
        if (obs["mesh"].get("mesh.slot") != "full"
                or obs["mesh"].get("mesh.width") != dev["count"]):
            raise SmokeFailure(f"lone transcode leased {obs['mesh']}, "
                               f"expected the full {dev['count']}-wide mesh")
        if snap["active"] or snap["pending"] or sched.quarantined_count():
            raise SmokeFailure(f"scheduler not idle/healthy after the "
                               f"drain: {snap}")

    # -- the published tree, by the repo's own validators
    tree = video_dir / res["ladder_video"]["slug"]
    manifest = integrity.load_manifest(tree)
    if manifest is None:
        raise SmokeFailure(f"{tree}: no outputs.json")
    problems = integrity.verify_tree(tree, manifest)
    if problems:
        raise SmokeFailure(f"verify_tree: {problems[:5]}")
    try:
        variants = hls.validate_master_playlist(tree / "master.m3u8")
        for name in rungs:
            stats = hls.validate_media_playlist(
                tree / name / "playlist.m3u8", expect_cmaf=True)
            if abs(stats["duration_s"] - n_frames / fps) > 1e-3:
                raise SmokeFailure(f"{name}: playlist covers "
                                   f"{stats['duration_s']}s of "
                                   f"{n_frames / fps}s")
    except hls.PlaylistValidationError as exc:
        raise SmokeFailure(f"playlist validation: {exc}") from exc
    if len(variants) != len(rungs):
        raise SmokeFailure(f"master lists {len(variants)} variants, "
                           f"ladder has {len(rungs)}")
    if not list((tree / "sprites").glob("*.jpg")):
        raise SmokeFailure("the sprite job left no sheet")
    t0 = time.monotonic()
    obs["decoded_psnr_y"] = {}
    for name in (rungs[0], rungs[-1]):
        psnr = decoded_psnr_y(tree / name, y4m, DECODE_FRAMES)
        obs["decoded_psnr_y"][name] = round(psnr, 2)
        if psnr < psnr_floor:
            raise SmokeFailure(f"{name}: decoded PSNR-Y {psnr:.2f} dB below "
                               f"the {psnr_floor} dB floor")
    _say(f"tree verified ({len(manifest)} files), playlists valid, decoded "
         f"PSNR-Y {obs['decoded_psnr_y']} in {time.monotonic() - t0:.1f}s")

    # -- captions
    vtt = video_dir / res["audio_video"]["slug"] / "captions.vtt"
    cues = parse_vtt(vtt.read_text())
    engine = peek_engine()
    windows = engine.windows_decoded if engine is not None else 0
    obs["asr"] = {"windows_decoded": windows, "cues": len(cues),
                  **(engine.stats() if engine is not None else {})}
    _say(f"captions.vtt parses ({len(cues)} cues); engine {obs['asr']}")
    if windows != 3:
        raise SmokeFailure(f"engine decoded {windows} windows, the "
                           f"{AUDIO_S:.0f}s track holds 3")
    reset_engine()

    if dev["platform"] == "tpu":
        obs["mosaic"] = mosaic_verdict()
        _say(f"fused Pallas plane under Mosaic: {obs['mosaic']}")
    return obs


def main() -> int:
    """Returns only to refuse (no TPU); a run ends in ``os._exit``."""
    t0 = time.monotonic()
    # Before vlog_tpu.config is imported. Deployment limits, not code
    # paths: the timeout envelope assumes a ladder near realtime, and on
    # the chip today the chain program runs ~400x slower than that
    # (PR 21), so the default floor of 300 s would cancel the transcode;
    # the disk-admission floor protects upload hosts, not a 2 GB smoke.
    os.environ.setdefault("VLOG_TIMEOUT_MULTIPLIER", "400")
    os.environ.setdefault("VLOG_MIN_FREE_DISK_GB", "4")
    try:
        import jax
    except ImportError as exc:
        print(f"chip_smoke: {exc}", file=sys.stderr)
        return 2
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {platform!r}; "
              "no result", file=sys.stderr)
        return 3
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    work = Path(tempfile.mkdtemp(prefix="vlog-chip-smoke-"))
    code = 1
    try:
        obs = run_smoke(work)
        _say(f"total wall {time.monotonic() - t0:.1f}s")
        print(json.dumps({"ok": True, "device": obs["device"]}), flush=True)
        code = 0
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
    except Exception:  # noqa: BLE001 — the boundary: report, exit non-zero
        import traceback

        traceback.print_exc()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    # A failed drain can leave a compute thread inside a dispatch that
    # nothing can interrupt; do not wait for it to say what already
    # failed. (No child process is alive here: gcc has long exited.)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


if __name__ == "__main__":
    sys.exit(main())
