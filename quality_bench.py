"""Quality parity bench: PSNR-at-target-bitrate vs the libx264 anchor.

VERDICT round-2 weak #2: "all-intra + VBR hits bitrate targets by
sacrificing quality, silently … make the all-intra gap a number." This
harness does exactly that: for each ladder rung it encodes the same
synthetic-but-temporally-redundant content with (a) the first-party
encoder through the production backend (closed-loop VBR at the rung's
ladder bitrate) and (b) libavcodec's libx264 at the same average bitrate
(the reference's CPU worker path, worker/hwaccel.py `-c:v libx264 -b:v`),
decodes both with the system libavcodec oracle, and reports PSNR-Y and
achieved bitrate side by side.

Usage: JAX_PLATFORMS=cpu python quality_bench.py [--frames N] [--rungs 360p,720p]
Writes QUALITY.md and prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

# A quality measurement is a CPU run: pin the platform before jax loads.
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

REPO = Path(__file__).parent
FIXTURES = REPO / "tests" / "fixtures"


def _have_encoder(name: str) -> bool:
    import ctypes
    import ctypes.util

    try:
        lib = ctypes.CDLL(ctypes.util.find_library("avcodec")
                          or "libavcodec.so")
        lib.avcodec_find_encoder_by_name.restype = ctypes.c_void_p
        return bool(lib.avcodec_find_encoder_by_name(name.encode()))
    except OSError:
        return False


def build_tool(name: str, tmp: Path) -> Path:
    cc = shutil.which("gcc") or shutil.which("cc")
    if cc is None:
        sys.exit("no C compiler")
    exe = tmp / name
    proc = subprocess.run(
        [cc, "-O2", "-o", str(exe), str(FIXTURES / f"{name}.c"),
         "-lavcodec", "-lavutil"], capture_output=True, text=True)
    if proc.returncode != 0:
        sys.exit(f"{name} build failed: {proc.stderr[:400]}")
    return exe


def moving_scene(n: int, h: int, w: int, *, seed: int = 0) -> np.ndarray:
    """I420 frames with real temporal structure: a panning textured
    background + moving objects + light sensor noise. Temporal redundancy
    is what separates inter from intra coding — pure noise would hide the
    gap, a static card would exaggerate it."""
    rng = np.random.default_rng(seed)
    # big textured world to pan across, at 2x resolution so the camera
    # pan lands on true sub-pixel phases (real footage moves fractionally;
    # integer-only panning would hide what sub-pel ME buys — for both
    # encoders: x264 has full quarter-pel and sees the same frames)
    wh, ww = (h + 256) * 2, (w + 256) * 2
    yy, xx = np.mgrid[0:wh, 0:ww]
    world = (96 + 60 * np.sin(xx / 34.0) * np.cos(yy / 46.0)
             + 40 * ((xx // 64 + yy // 64) % 2)
             + rng.normal(0, 3.0, (wh, ww))).astype(np.float32)
    frames = np.empty((n, h * w * 3 // 2), np.uint8)
    # scene cuts every ~4 s: encoders must recover from a full-frame
    # change mid-chain (panning alone never stresses that path); noise
    # bursts model sensor gain-ups / confetti that break rate control
    # on real footage
    cut_every = 96
    for t in range(n):
        cut = (t // cut_every) % 2
        ox = (int(4.2 * t) + cut * 977) % 512    # cuts jump the camera
        oy = (int(2.6 * t) + cut * 491) % 512
        y = world[oy:oy + 2 * h:2, ox:ox + 2 * w:2].copy()
        if cut:
            y = 255.0 - y                        # hard visual change
        # two moving objects
        bx = int((w - 80) * (0.5 + 0.4 * np.sin(t / 14.0)))
        by = int((h - 80) * (0.5 + 0.4 * np.cos(t / 19.0)))
        y[by:by + 64, bx:bx + 64] = 210.0
        bx2 = int((w - 48) * (0.5 + 0.45 * np.cos(t / 9.0)))
        y[h // 4:h // 4 + 32, bx2:bx2 + 32] = 40.0
        burst = 6.0 if (t % 64) >= 58 else 1.5   # periodic noise bursts
        y += rng.normal(0, burst, y.shape)
        yq = np.clip(y, 0, 255).astype(np.uint8)
        u = np.full((h // 2, w // 2), 118, np.uint8)
        v = np.full((h // 2, w // 2), 138, np.uint8)
        u[by // 2:(by + 64) // 2, bx // 2:(bx + 64) // 2] = 90
        v[by // 2:(by + 64) // 2, bx // 2:(bx + 64) // 2] = 160
        frames[t] = np.concatenate([yq.ravel(), u.ravel(), v.ravel()])
    return frames


def psnr_y(ref: np.ndarray, dec: np.ndarray, h: int, w: int) -> float:
    n = min(ref.shape[0], dec.shape[0])
    ys = ref[:n, :h * w].astype(np.float64)
    yd = dec[:n, :h * w].astype(np.float64)
    mse = np.mean((ys - yd) ** 2)
    return 10 * np.log10(255.0 ** 2 / max(mse, 1e-9))


def decode_annexb(avdec: Path, annexb: Path, h: int, w: int,
                  tmp: Path, codec: str = "h264") -> np.ndarray:
    out = tmp / "dec.yuv"
    subprocess.run([str(avdec), str(annexb), str(out), codec], check=True,
                   capture_output=True)
    data = np.fromfile(out, np.uint8)
    fs = h * w * 3 // 2
    return data[: len(data) // fs * fs].reshape(-1, fs)


def write_scene_y4m(frames, h: int, w: int, path: Path, fps: int) -> None:
    """Serialize packed I420 scene frames once per rung (shared by the
    production-encode paths and the codec-specific sections)."""
    from vlog_tpu.media.y4m import write_y4m

    fs = h * w
    write_y4m(path, [
        (f[:fs].reshape(h, w),
         f[fs:fs + fs // 4].reshape(h // 2, w // 2),
         f[fs + fs // 4:].reshape(h // 2, w // 2))
        for f in frames
    ], fps_num=fps, fps_den=1)


def run_ours(frames: np.ndarray, h: int, w: int, fps: int, rung,
             tmp: Path, avdec: Path) -> dict:
    """Encode through the production backend; decode with the oracle."""
    from vlog_tpu.worker.pipeline import process_video

    y4m = tmp / "src.y4m"
    write_scene_y4m(frames, h, w, y4m, fps)
    out = tmp / "ours"
    t0 = time.perf_counter()
    result = process_video(y4m, out, audio=False, thumbnail=False,
                           rungs=(rung,))
    wall = time.perf_counter() - t0
    rr = result.run.rungs[0]
    # concatenate samples from segments into annex-b for the oracle
    from vlog_tpu.media.boxes import parse_box_tree

    annexb = bytearray()
    rdir = out / rung.name
    from vlog_tpu.codecs.h264.syntax import annexb as to_annexb  # noqa: F401

    # init: SPS/PPS from avcC
    init = (rdir / "init.mp4").read_bytes()
    idx = init.find(b"avcC")
    size = int.from_bytes(init[idx - 4:idx], "big")
    avcc = init[idx + 4: idx - 4 + size]
    # parse avcC: sps/pps
    nsps = avcc[5] & 0x1F
    off = 6
    for _ in range(nsps):
        ln = int.from_bytes(avcc[off:off + 2], "big")
        annexb += b"\x00\x00\x00\x01" + avcc[off + 2:off + 2 + ln]
        off += 2 + ln
    npps = avcc[off]
    off += 1
    for _ in range(npps):
        ln = int.from_bytes(avcc[off:off + 2], "big")
        annexb += b"\x00\x00\x00\x01" + avcc[off + 2:off + 2 + ln]
        off += 2 + ln
    for seg in sorted(rdir.glob("segment_*.m4s")):
        data = seg.read_bytes()
        with open(seg, "rb") as fp:
            tree = parse_box_tree(fp)
        mdat = next(b for b in tree if b.type == "mdat")
        payload = data[mdat.offset + 8: mdat.offset + mdat.size]
        off = 0
        while off < len(payload):
            ln = int.from_bytes(payload[off:off + 4], "big")
            annexb += b"\x00\x00\x00\x01" + payload[off + 4:off + 4 + ln]
            off += 4 + ln
    bpath = tmp / "ours.h264"
    bpath.write_bytes(bytes(annexb))
    dec = decode_annexb(avdec, bpath, h, w, tmp)
    from vlog_tpu import config as _cfg

    mode = (f"vlog-tpu (I+P chains, gop={_cfg.GOP_LEN})"
            if _cfg.GOP_MODE == "p" else "vlog-tpu (all-intra)")
    return {
        "encoder": mode,
        "bitrate_kbps": rr.achieved_bitrate // 1000,
        "psnr_y": round(psnr_y(frames, dec, h, w), 2),
        "wall_s": round(wall, 1),
    }


def run_ours_h265(frames: np.ndarray, h: int, w: int, y4m: Path, rung,
                  tmp: Path, avdec: Path) -> dict:
    """codec=h265 through the production backend (I + quarter-pel P
    chains); decode the hvc1 CMAF tree with the oracle. ``y4m`` is the
    source run_ours already serialized for the same rung."""
    from vlog_tpu.media.boxes import parse_box_tree
    from vlog_tpu.worker.pipeline import process_video

    out = tmp / "ours265"
    t0 = time.perf_counter()
    result = process_video(y4m, out, audio=False, thumbnail=False,
                           rungs=(rung,), codec="h265")
    wall = time.perf_counter() - t0
    rr = result.run.rungs[0]
    rdir = out / rung.name
    init = (rdir / "init.mp4").read_bytes()
    i = init.index(b"hvcC")
    hvcc = init[i + 4:i - 4 + int.from_bytes(init[i - 4:i], "big")]
    pos, annexb = 22, bytearray()
    n_arrays = hvcc[pos]; pos += 1
    for _ in range(n_arrays):
        pos += 1
        cnt = int.from_bytes(hvcc[pos:pos + 2], "big"); pos += 2
        for _ in range(cnt):
            ln = int.from_bytes(hvcc[pos:pos + 2], "big"); pos += 2
            annexb += b"\x00\x00\x00\x01" + hvcc[pos:pos + ln]; pos += ln
    for seg in sorted(rdir.glob("segment_*.m4s")):
        data = seg.read_bytes()
        with open(seg, "rb") as fp:
            tree = parse_box_tree(fp)
        mdat = next(b for b in tree if b.type == "mdat")
        payload = data[mdat.offset + 8: mdat.offset + mdat.size]
        p = 0
        while p < len(payload):
            ln = int.from_bytes(payload[p:p + 4], "big"); p += 4
            annexb += b"\x00\x00\x00\x01" + payload[p:p + ln]; p += ln
    bpath = tmp / "ours.hevc"
    bpath.write_bytes(bytes(annexb))
    dec = decode_annexb(avdec, bpath, h, w, tmp, codec="hevc")
    return {
        "encoder": "vlog-tpu h265 (I + quarter-pel P chains)",
        "bitrate_kbps": rr.achieved_bitrate // 1000,
        "psnr_y": round(psnr_y(frames, dec, h, w), 2),
        "wall_s": round(wall, 1),
    }


def run_x264(frames: np.ndarray, h: int, w: int, fps: int, bps: int,
             tmp: Path, x264: Path, avdec: Path, preset: str = "medium",
             encoder: str = "libx264") -> dict:
    """Anchor encode at the same average bitrate (libx264 by default,
    libx265 for the HEVC anchor) + oracle decode + PSNR."""
    raw = tmp / "src.yuv"
    if not (raw.exists() and raw.stat().st_size == frames.nbytes):
        frames.tofile(raw)      # shared between x264/x265 anchor calls
    out = tmp / f"{encoder}.bin"
    t0 = time.perf_counter()
    proc = subprocess.run([str(x264), str(raw), str(w), str(h), str(fps),
                           str(bps), preset, str(out), encoder],
                          capture_output=True, text=True)
    if proc.returncode != 0:
        sys.exit(f"anchor encode failed ({encoder}): "
                 f"{proc.stderr.strip()[:300]}")
    wall = time.perf_counter() - t0
    dec = decode_annexb(avdec, out, h, w, tmp,
                        codec="hevc" if encoder == "libx265" else "h264")
    dur = frames.shape[0] / fps
    return {
        "encoder": f"{encoder} -preset {preset}",
        "bitrate_kbps": int(out.stat().st_size * 8 / dur) // 1000,
        "psnr_y": round(psnr_y(frames, dec, h, w), 2),
        "wall_s": round(wall, 1),
    }


def run_ours_av1(frames: np.ndarray, h: int, w: int, y4m: Path, rung,
                 tmp: Path) -> dict | None:
    """codec=av1 through the product plane (delegated system encoder,
    backends/av1_path.py); round-trip the av01 CMAF tree through the
    libav shim for PSNR. None when the host has no AV1 encoder."""
    from vlog_tpu.backends.source import open_source
    from vlog_tpu.native.avbuild import get_av_lib
    from vlog_tpu.worker.pipeline import process_video

    lib = get_av_lib()
    if lib is None:
        print("av1: libav shim unavailable", file=sys.stderr)
        return None
    hdl = lib.vt_av1_open(64, 64, 24, 1, 200_000, 8, 8)
    if not hdl:
        print("av1: no system AV1 encoder in libavcodec", file=sys.stderr)
        return None
    lib.vt_av1_close(hdl)

    out = tmp / "oursav1"
    t0 = time.perf_counter()
    result = process_video(y4m, out, audio=False, thumbnail=False,
                           rungs=(rung,), codec="av1")
    wall = time.perf_counter() - t0
    rr = result.run.rungs[0]
    rdir = out / rung.name
    stream = tmp / "av1round.mp4"
    stream.write_bytes((rdir / "init.mp4").read_bytes() + b"".join(
        s.read_bytes() for s in sorted(rdir.glob("segment_*.m4s"))))
    src = open_source(stream)
    try:
        dec = []
        for y, u, v in src.read_batches(16):
            for i in range(y.shape[0]):
                dec.append(np.concatenate([
                    np.asarray(y[i]).ravel(), np.asarray(u[i]).ravel(),
                    np.asarray(v[i]).ravel()]))
    finally:
        src.close()
    if not dec:
        print("av1: shim could not decode the av01 round-trip; skipping",
              file=sys.stderr)
        return None
    dec_arr = np.stack(dec)
    return {
        "encoder": "delegated system AV1 (libaom/SVT via av1_path)",
        "bitrate_kbps": rr.achieved_bitrate // 1000,
        "psnr_y": round(psnr_y(frames, dec_arr, h, w), 2),
        "wall_s": round(wall, 1),
    }


def wer(ref_words: list[str], hyp_words: list[str]) -> float:
    """Word error rate: Levenshtein(ref, hyp) / len(ref)."""
    n, m = len(ref_words), len(hyp_words)
    if n == 0:
        return 0.0 if m == 0 else float("inf")
    prev = list(range(m + 1))
    for i in range(1, n + 1):
        cur = [i] + [0] * m
        for j in range(1, m + 1):
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1,
                         prev[j - 1] + (ref_words[i - 1] != hyp_words[j - 1]))
        prev = cur
    return prev[m] / n


def _norm_words(text: str) -> list[str]:
    import re

    return re.findall(r"[a-z0-9']+", text.lower())


def run_asr(audio_path: str, ref_path: str, beam: int) -> dict:
    """Transcribe ``audio_path`` with VLOG_WHISPER_DIR weights and score
    WER against the reference transcript — the north-star caption metric
    (BASELINE config #4: WER parity with faster-whisper beam-5 + VAD).
    Runs the full production path: VAD -> mel -> batched beam decode ->
    cue stitching."""
    import numpy as np

    from vlog_tpu import config
    from vlog_tpu.asr import mel as melmod
    from vlog_tpu.asr.engine import get_engine, reset_engine
    from vlog_tpu.media.audio import extract_audio, resample, to_mono
    from vlog_tpu.worker.transcribe import transcribe_audio_engine

    model_dir = config.WHISPER_DIR or os.environ.get("VLOG_WHISPER_DIR")
    if not model_dir:
        sys.exit("asr bench needs VLOG_WHISPER_DIR pointing at Whisper "
                 "weights (HF layout); none configured")
    audio = extract_audio(audio_path)
    if audio is None or not audio.pcm.size:
        sys.exit(f"{audio_path}: no audio track")
    audio = resample(to_mono(audio), melmod.SAMPLE_RATE)
    samples = np.ascontiguousarray(audio.pcm[0], np.float32)
    config.WHISPER_BEAM = beam
    # The production decode path: windows through the shared continuous-
    # batching engine (weights memoized, fixed-shape bucketed batches).
    engine = get_engine(model_dir)
    stats: dict = {}
    t0 = time.perf_counter()
    try:
        cues, language, n_windows = transcribe_audio_engine(
            samples, engine, job_key="quality-bench", beam=beam,
            stats_out=stats)
    finally:
        reset_engine()
    wall = time.perf_counter() - t0
    hyp = " ".join(c.text for c in cues)
    ref = Path(ref_path).read_text()
    score = wer(_norm_words(ref), _norm_words(hyp))
    decoded = stats.get("windows_submitted", n_windows)
    return {
        # bench.py gate-record shape: metric/value/unit/vs_baseline, so
        # the orchestrator can consume the last JSON line directly.
        "metric": "asr_wer", "value": round(score, 4), "unit": "wer",
        "vs_baseline": 0.0,
        "beam": beam, "language": language,
        "audio_s": round(len(samples) / 16_000, 1),
        "wall_s": round(wall, 1),
        "windows": n_windows,
        "windows_decoded": decoded,
        "windows_per_s": round(decoded / wall, 3) if wall > 0 else 0.0,
        "hyp_words": len(_norm_words(hyp)),
        "ref_words": len(_norm_words(ref)),
    }


def run_asr_quant(beam: int) -> dict:
    """WER-parity gate for VLOG_WHISPER_QUANT=int8 — synthetic-weights
    identity proxy, documented as such.

    This environment ships no Whisper checkpoint, so the gate cannot
    score real speech. Instead it constructs random HF-shaped weights
    whose linear projections sit EXACTLY on the int8 grid (w = q * 2^-9
    with a forced ±127 entry per output row). The production
    ``quantize_params`` then recovers (q, scale) losslessly, and because
    power-of-two scaling is exact in f32 and distributes over the
    matmul's summation order, the dequant-on-use decode is bitwise
    identical to the f32 decode — so the proxy's PASS bar is WER == 0.0
    (token-for-token), far stricter than the relaxed parity a real
    checkpoint would gate at. What it proves: the int8 plumbing
    (quantize -> QuantTensor pytree -> dequant matmul -> KV-cached scan)
    changes nothing it shouldn't. What it cannot prove: real-weights WER
    degradation, which needs VLOG_WHISPER_DIR and the --asr mode.
    """
    import jax.numpy as jnp
    import numpy as np

    from vlog_tpu.asr import decode as dec
    from vlog_tpu.asr.load import _QUANT_KEY, quantize_params
    from vlog_tpu.asr.model import WhisperConfig, init_random_params

    cfg = WhisperConfig(
        d_model=64, encoder_layers=2, decoder_layers=2,
        encoder_attention_heads=4, decoder_attention_heads=4,
        encoder_ffn_dim=128, decoder_ffn_dim=128, vocab_size=128,
        num_mel_bins=80, max_source_positions=1500,
        max_target_positions=448)
    params = init_random_params(cfg, seed=0)
    # Snap every quantizable projection onto the int8 grid: scale 2^-9
    # covers the 0.02-stdev init range within ±127 steps.
    grid = 2.0 ** -9
    snapped = {}
    for k, v in params.items():
        if _QUANT_KEY.search(k) and v.ndim == 2:
            q = np.clip(np.round(np.asarray(v) / grid), -127, 127)
            q[:, 0] = 127.0      # pins amax so scale recovers exactly
            snapped[k] = jnp.asarray((q * grid).astype(np.float32))
        else:
            snapped[k] = v
    qparams = quantize_params(snapped, "int8")

    rng = np.random.default_rng(7)
    mel = jnp.asarray(rng.standard_normal((2, 80, 3000)), jnp.float32)
    prompt = jnp.asarray([3, 4], jnp.int32)
    zeros = jnp.zeros(cfg.vocab_size, jnp.float32)
    max_new = 24
    kw = dict(cfg=cfg, sot=3, eot=1, ts_begin=cfg.vocab_size - 2,
              no_speech=-1, max_new=max_new, timestamps=False, beam=1)

    def decode_with(p):
        cache = dec.kv_pool.lease(cfg, mel.shape[0],
                                  prompt.shape[0] + max_new)
        toks, _, cache = dec._generate_beam_jit(p, mel, prompt, zeros,
                                                zeros, cache, **kw)
        dec.kv_pool.release(cache)
        return np.asarray(toks)

    t0 = time.perf_counter()
    ref_toks = decode_with(snapped)
    f32_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    hyp_toks = decode_with(qparams)
    int8_wall = time.perf_counter() - t0
    # token-level WER between the f32 and int8 decodes (each token a
    # "word"); the identity proxy demands exactly 0.0
    scores = [wer([str(t) for t in r], [str(t) for t in h])
              for r, h in zip(ref_toks.tolist(), hyp_toks.tolist())]
    score = max(scores)
    return {
        "metric": "asr_wer_quant", "value": round(score, 4), "unit": "wer",
        "vs_baseline": 0.0,
        "quant": "int8", "beam": beam, "gate": "identity_proxy",
        "identical_tokens": bool(np.array_equal(ref_toks, hyp_toks)),
        "windows": int(mel.shape[0]), "max_new": max_new,
        "f32_wall_s": round(f32_wall, 3),
        "int8_wall_s": round(int8_wall, 3),
        "note": ("synthetic int8-grid weights: proves the quantized "
                 "decode plumbing is lossless on representable weights; "
                 "real-WER parity needs VLOG_WHISPER_DIR + --asr"),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=96)
    ap.add_argument("--fps", type=int, default=24)
    ap.add_argument("--rungs", default="360p,480p,720p")
    ap.add_argument("--h265", action="store_true",
                    help="add a codec=h265 row for the first rung")
    ap.add_argument("--h265-rungs", default="",
                    help="comma list: codec=h265 rows vs a libx265 "
                         "anchor at the same bitrate")
    ap.add_argument("--av1-rungs", default="",
                    help="comma list: delegated codec=av1 rows")
    ap.add_argument("--append", action="store_true",
                    help="append sections to QUALITY.md instead of "
                         "rewriting it")
    ap.add_argument("--skip-h264", action="store_true",
                    help="skip the H.264-vs-x264 base rows (codec-"
                         "specific runs reuse --rungs for geometry only)")
    ap.add_argument("--asr", metavar="AUDIO",
                    help="WER mode: transcribe AUDIO (wav/mp4) with "
                         "VLOG_WHISPER_DIR weights instead of video PSNR")
    ap.add_argument("--ref", metavar="TXT",
                    help="reference transcript for --asr")
    ap.add_argument("--beam", type=int, default=5)
    ap.add_argument("--quant", action="store_true",
                    help="with --asr: int8 WER-parity gate (synthetic-"
                         "weights identity proxy; no checkpoint needed)")
    args = ap.parse_args()

    if args.quant:
        rec = run_asr_quant(args.beam)
        print(json.dumps(rec))
        return

    if args.asr:
        if not args.ref:
            sys.exit("--asr requires --ref transcript.txt")
        rec = run_asr(args.asr, args.ref, args.beam)
        print(json.dumps(rec))
        return

    from vlog_tpu import config

    tmp = Path(tempfile.mkdtemp(prefix="vlog-quality-"))
    avdec = build_tool("avdec", tmp)
    x264 = build_tool("x264enc", tmp)

    geoms = {"360p": (360, 640), "480p": (480, 854), "720p": (720, 1280),
             "1080p": (1080, 1920), "1440p": (1440, 2560),
             "2160p": (2160, 3840)}

    def scene_for(rung):
        g = geoms[rung.name]
        h, w = g[0], g[1] - g[1] % 16
        return h, w, moving_scene(args.frames, h, w)

    rows = []
    h265_rows = []
    av1_rows = []
    h265_wanted = {s.strip() for s in args.h265_rungs.split(",")
                   if s.strip()}
    av1_wanted = {s.strip() for s in args.av1_rungs.split(",")
                  if s.strip()}
    rung_names = [s.strip() for s in args.rungs.split(",") if s.strip()]
    if (h265_wanted or args.h265) and not _have_encoder("libx265"):
        print("libx265 not in system libavcodec; skipping HEVC anchor "
              "rows", file=sys.stderr)
        h265_wanted = set()
        args.h265 = False
    stray = (h265_wanted | av1_wanted) - set(rung_names)
    if stray:
        sys.exit(f"--h265-rungs/--av1-rungs entries {sorted(stray)} are "
                 f"not in --rungs {rung_names} (codec rows piggyback on "
                 "the per-rung scene/geometry loop)")
    for name in rung_names:
        rung = config.LADDER_BY_NAME[name]
        h, w, frames = scene_for(rung)
        rtmp = tmp / rung.name
        rtmp.mkdir()
        if args.skip_h264:
            # codec-specific sections still need the serialized source
            write_scene_y4m(frames, h, w, rtmp / "src.y4m", args.fps)
        else:
            ours = run_ours(frames, h, w, args.fps, rung, rtmp, avdec)
            anchor = run_x264(frames, h, w, args.fps, rung.video_bitrate,
                              rtmp, x264, avdec)
            rows.append({"rung": rung.name,
                         "target_kbps": rung.video_bitrate // 1000,
                         "ours": ours, "x264": anchor,
                         "psnr_gap_db": round(
                             anchor["psnr_y"] - ours["psnr_y"], 2)})
            print(f"{rung.name}: ours {ours['psnr_y']} dB @ "
                  f"{ours['bitrate_kbps']} kbps | x264 "
                  f"{anchor['psnr_y']} dB @ "
                  f"{anchor['bitrate_kbps']} kbps", file=sys.stderr)
        if args.h265 and not h265_rows and not h265_wanted:
            h265_wanted = {rung.name}        # legacy flag: first rung
        if rung.name in h265_wanted:
            ours265 = run_ours_h265(frames, h, w, rtmp / "src.y4m",
                                    rung, rtmp, avdec)
            x265 = run_x264(frames, h, w, args.fps, rung.video_bitrate,
                            rtmp, x264, avdec, encoder="libx265")
            h265_rows.append({
                "rung": rung.name,
                "target_kbps": rung.video_bitrate // 1000,
                "ours": ours265, "x265": x265,
                "psnr_gap_db": round(x265["psnr_y"] - ours265["psnr_y"],
                                     2)})
            print(f"{rung.name} h265: ours {ours265['psnr_y']} dB @ "
                  f"{ours265['bitrate_kbps']} kbps | x265 "
                  f"{x265['psnr_y']} dB @ {x265['bitrate_kbps']} kbps",
                  file=sys.stderr)
        if rung.name in av1_wanted:
            av1 = run_ours_av1(frames, h, w, rtmp / "src.y4m", rung, rtmp)
            if av1 is None:
                print(f"{rung.name} av1: unavailable (see message above);"
                      " skipping row", file=sys.stderr)
            else:
                av1_rows.append({
                    "rung": rung.name,
                    "target_kbps": rung.video_bitrate // 1000, **av1})
                print(f"{rung.name} av1: {av1['psnr_y']} dB @ "
                      f"{av1['bitrate_kbps']} kbps", file=sys.stderr)
    qpath = REPO / "QUALITY.md"
    appending = args.append and qpath.exists()
    lines = []
    if not appending:
        lines += [
            "# Quality parity: PSNR at the ladder bitrate vs libx264",
            "",
            "Content: synthetic panning scene with moving objects"
            + (", scene cuts" if args.frames > 96 else "")
            + (" and noise bursts" if args.frames >= 64 else "")
            + f" ({args.frames} frames @ {args.fps} fps). Decoded by the "
            "system libavcodec oracle; PSNR-Y vs the pristine source.",
            "",
        ]
    if rows:
        lines += [
            f"## H.264 vs libx264-medium ({args.frames} frames @ "
            f"{args.fps} fps)",
            "",
            "| rung | target | ours kbps | ours PSNR-Y | x264 kbps | "
            "x264 PSNR-Y | gap (dB) |",
            "|---|---|---|---|---|---|---|",
        ]
        for r in rows:
            lines.append(
                f"| {r['rung']} | {r['target_kbps']}k "
                f"| {r['ours']['bitrate_kbps']} | {r['ours']['psnr_y']} "
                f"| {r['x264']['bitrate_kbps']} | {r['x264']['psnr_y']} "
                f"| {r['psnr_gap_db']} |")
        lines.append("")
    if h265_rows:
        lines += [
            f"## First-party HEVC (codec=h265) vs libx265-medium "
            f"({args.frames} frames @ {args.fps} fps)",
            "",
            "| rung | target | ours kbps | ours PSNR-Y | x265 kbps | "
            "x265 PSNR-Y | gap (dB) |",
            "|---|---|---|---|---|---|---|",
        ]
        for r in h265_rows:
            lines.append(
                f"| {r['rung']} | {r['target_kbps']}k "
                f"| {r['ours']['bitrate_kbps']} | {r['ours']['psnr_y']} "
                f"| {r['x265']['bitrate_kbps']} | {r['x265']['psnr_y']} "
                f"| {r['psnr_gap_db']} |")
        lines.append("")
    if av1_rows:
        lines += [
            f"## Delegated AV1 (codec=av1, system encoder through "
            f"av1_path) ({args.frames} frames @ {args.fps} fps)",
            "",
            "| rung | target | kbps | PSNR-Y | encoder |",
            "|---|---|---|---|---|",
        ]
        for r in av1_rows:
            lines.append(
                f"| {r['rung']} | {r['target_kbps']}k "
                f"| {r['bitrate_kbps']} | {r['psnr_y']} "
                f"| {r['encoder']} |")
        lines.append("")
    lines += [f"Generated by quality_bench.py "
              f"(frames={args.frames}, fps={args.fps}).", ""]
    if appending:
        qpath.write_text(qpath.read_text() + "\n" + "\n".join(lines))
    else:
        qpath.write_text("\n".join(lines))
    rec = {"metric": "psnr_gap_vs_x264_db",
           "value": (max(r["psnr_gap_db"] for r in rows) if rows
                     else None),
           "unit": "dB_worst_rung",
           "rows": rows}
    if h265_rows:
        rec["h265_rows"] = h265_rows
        rec["h265_worst_gap_db"] = max(r["psnr_gap_db"]
                                       for r in h265_rows)
    if av1_rows:
        rec["av1_rows"] = av1_rows
    print(json.dumps(rec))
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
