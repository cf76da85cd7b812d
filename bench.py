"""Benchmark: 4K -> 6-rung CMAF ladder, single TPU chip.

Headline metric (BASELINE.json config #2): the PRODUCTION device ladder
— per-rung lanczos resize + the I+P chain H.264 DSP with spec in-loop
deblocking for ALL six rungs in one XLA program (exactly what
``JaxBackend.run`` dispatches in the default GOP_MODE="p" config,
``ladder_chain_program(search=MOTION_SEARCH, deblock=True)``) — as a
realtime multiple at 30 fps; vs_baseline divides by the NVENC worker's
estimated ~1.0x full-ladder throughput (see below). The intra-only
ladder earlier rounds headlined is kept as a secondary line
(``intra_device_realtime_x``).

A separate always-on-CPU body measures the HOST entropy stage (CABAC
slice coding of real chain-program levels at the ladder's calibrated
operating point) in macroblocks/s — a host property independent of the
accelerator — and projects it onto the 4K ladder's MB/frame. The
derived ``coloc_e2e_estimate_x`` is min(device chain throughput,
entropy throughput) at 30 fps: on co-located hardware the two stages
overlap (one-batch-in-flight), so steady state is bounded by the
slower stage, with packaging ~free. Entropy scales ~linearly with host
cores (the C coders release the GIL; frames are independent): measured
~1.3M MB/s PER vCPU = 21.7 fps of full 4K 6-rung ladder per core, so
on real TPU hosts (100+ vCPUs) the device stage is the bound — this
1-vCPU driver VM reports the per-core floor.

The END-TO-END wall clock through the production backend (host Y4M
decode via the prefetch thread -> device I+P chain ladder -> CABAC host
entropy -> fMP4 packaging) is reported alongside as ``e2e_realtime_x``,
in the PRODUCTION configuration: gop_mode=p (24-frame chains), CABAC,
closed-loop VBR — not the intra shortcut earlier rounds measured. A
per-stage wall-clock breakdown (decode_wait / compute_wait /
device_pull / entropy / package, from RunResult.stage_s) says where the
time went — compute_wait is pure device compute (block_until_ready),
device_pull the device->host transfer after readiness. stage_s also
carries the pipeline executor's overlap gauges (pipeline_depth /
max_in_flight / host_busy_s / host_wall_s / host_occupancy,
parallel/executor.py): the stage fields are per-stage BUSY sums, the
gauges say how much of that busy time ran concurrently — host_busy_s
above host_wall_s (occupancy > 1) means the per-rung fan-out and the
VLOG_PIPELINE_DEPTH-deep in-flight window are overlapping for real.

vs_baseline: the reference's only published numbers are single-rung
1080p NVENC encode speeds (docs/ARCHITECTURE.md:216-225: h264_nvenc
3.74x realtime on an RTX 3090) with ~2x gain from parallel quality
encoding (docs/CONFIGURATION.md:432). Scaling 3.74x by the 4x pixel
ratio 1080p->4K and the ~1.8x total-ladder pixel multiplier, with the
2x parallel-session gain, puts the NVENC worker's full-4K-ladder
throughput at ~1.0x realtime — the denominator used here.

Process layout: the parent process never imports JAX, because a chip
belongs to one process at a time. It starts exactly one child that opens
the chip (``--body``) and, after it, one CPU-pinned child for the host
entropy stage (``--entropy``). The device body goes first because it
answers in seconds when there is no TPU: bench.py then exits non-zero
and prints no record — a number from XLA:CPU is never published under a
device metric. On a body timeout the parent harvests whatever JSON
lines the body already printed (the device record is published the
moment it completes) instead of discarding a finished measurement.
"""

import json
import os
import subprocess
import sys
import time

NVENC_FULL_LADDER_REALTIME = 1.0   # see module docstring

TPU_TIMEOUT_S = 900
CPU_TIMEOUT_S = 900


# ---------------------------------------------------------------------------
# Measurement body (runs in the one subprocess that opens the chip)
# ---------------------------------------------------------------------------

def _structured_frames(rng, n, h, w):
    """Gradient blocks + per-frame horizontal shift + noise: enough
    structure for prediction and enough residual for real entropy load."""
    import numpy as np

    yy, xx = np.mgrid[0:h, 0:w]
    base = ((yy // 8 + xx // 8) % 256).astype(np.int16)
    y = np.stack([
        np.clip(np.roll(base, i, axis=1)
                + rng.integers(-20, 20, base.shape), 0, 255).astype(np.uint8)
        for i in range(n)])
    u = rng.integers(0, 256, (n, h // 2, w // 2)).astype(np.uint8)
    v = rng.integers(0, 256, (n, h // 2, w // 2)).astype(np.uint8)
    return y, u, v


def _ladder_rungs(plan_rung_geometry, ladder, src_h, src_w):
    return tuple(
        (r.name, p.height, p.width, r.base_qp)
        for r in ladder
        for p in [plan_rung_geometry(src_w, src_h, r)]
    )


def _chain_qps(np, rungs, clen):
    """Per-rung QP schedule for one chain: base QP with the production
    I-frame anchor offset (jax_backend.py dispatch does the same -2)."""
    qps = {}
    for name, h, w, base_qp in rungs:
        q = np.full((1, clen), base_qp, np.int32)
        q[:, 0] = np.maximum(q[:, 0] - 2, 0)
        qps[name] = q
    return qps


def _chain_rc(np, rungs, fps):
    """Device-RC params matching production (jax_backend dispatch):
    alpha > 0 so the measured program includes the in-chain adaptation
    the backend always runs once calibrated."""
    return {name: {"budget": np.float32(1e6 / fps), "alpha": np.float32(0.02)}
            for name, h, w, base_qp in rungs}


def run_body() -> None:
    import jax

    # Never publish anything but a TPU run under the TPU metric: refuse,
    # print no record, and let the parent exit non-zero.
    kind = jax.devices()[0].platform
    if kind != "tpu":
        print(f"bench: device body needs a TPU, found platform {kind!r}",
              file=sys.stderr)
        raise SystemExit(3)

    import numpy as np

    from vlog_tpu import config
    from vlog_tpu.backends.base import plan_rung_geometry
    from vlog_tpu.ops.pallas_ladder import use_pallas
    from vlog_tpu.parallel.compile_cache import (compile_seconds,
                                                 ensure_compile_cache)
    from vlog_tpu.parallel.ladder import (ladder_chain_program,
                                          single_chip_ladder)

    ensure_compile_cache()

    src_h, src_w, fps = 2160, 3840, 30.0
    chain_iters, intra_n, intra_iters = 3, 8, 6
    ladder = config.QUALITY_LADDER
    metric = "4k_6rung_chain_ladder_device_realtime_x"

    rungs = _ladder_rungs(plan_rung_geometry, ladder, src_h, src_w)
    rng = np.random.default_rng(0)

    # ---- PRIMARY: the production chain program. One chain of GOP_LEN
    # frames per dispatch is exactly the single-chip dispatch shape
    # JaxBackend.run uses (frame_batch=8 < GOP_LEN -> chains_per=1).
    clen = config.GOP_LEN
    fn, mats = ladder_chain_program(
        rungs, src_h, src_w, search=config.MOTION_SEARCH_RADIUS,
        deblock=config.H264_DEBLOCK)
    y, u, v = _structured_frames(rng, clen, src_h, src_w)
    qps = _chain_qps(np, rungs, clen)
    rc = _chain_rc(np, rungs, fps)
    cy, cu, cv, cmats, cqps, crc = jax.device_put(
        (y[None], u[None], v[None], mats, qps, rc))

    out = jax.block_until_ready(fn(cy, cu, cv, cmats, cqps, crc))  # compile
    t0 = time.perf_counter()
    for _ in range(chain_iters):
        out = jax.block_until_ready(fn(cy, cu, cv, cmats, cqps, crc))
    chain_dt = (time.perf_counter() - t0) / chain_iters
    chain_fps = clen / chain_dt
    realtime_x = chain_fps / fps

    vs = realtime_x / NVENC_FULL_LADDER_REALTIME
    unit = f"x_realtime_30fps_single_chip_{jax.devices()[0].platform}"
    # Stamp the mesh shape the backend would resolve for this ladder on
    # the visible devices (the 2-D data x rung layout), so BENCH records
    # from different rounds say what grid their numbers ran on.
    from vlog_tpu.parallel.mesh import resolve_mesh_shape
    n_dev = len(jax.devices())
    try:
        mesh_shape = (resolve_mesh_shape(None, n_dev, rungs).label
                      if n_dev > 1 else "1x1")
    except ValueError:
        mesh_shape = "1x1"
    record = {
        "metric": metric,
        "value": round(realtime_x, 3),
        "unit": unit,
        "vs_baseline": round(vs, 3),
        "mesh_shape": mesh_shape,
        "mesh_spec": config.TPU_MESH_SPEC,
        "chain_fps": round(chain_fps, 2),
        "chain_gop_len": clen,
        "chain_deblock": bool(config.H264_DEBLOCK),
        "chain_search": config.MOTION_SEARCH_RADIUS,
        # raw-speed plane stamps: which kernel plane ran, which Whisper
        # quant mode is configured, and this process's cumulative XLA
        # backend-compile seconds (warm restarts with the persistent
        # cache armed show a fraction of cold ones).
        "pallas": use_pallas(),
        "whisper_quant": config.WHISPER_QUANT,
        "compile_s": round(compile_seconds(), 3),
    }
    del out
    # Publish the completed device measurement IMMEDIATELY: if anything
    # below stalls, the orchestrator still harvests this line instead
    # of discarding a finished TPU run (the last JSON line on stdout
    # wins; timeouts re-read partial stdout).
    print(json.dumps(record), flush=True)

    # ---- SECONDARY: intra-only ladder (rounds 1-4's headline, kept for
    # cross-round continuity).
    ifn, imats = single_chip_ladder(rungs, src_h, src_w)
    iy, iu, iv = _structured_frames(rng, intra_n, src_h, src_w)
    iy, iu, iv, imats = jax.device_put((iy, iu, iv, imats))
    iout = jax.block_until_ready(ifn(iy, iu, iv, imats))
    t0 = time.perf_counter()
    for _ in range(intra_iters):
        iout = jax.block_until_ready(ifn(iy, iu, iv, imats))
    intra_dt = (time.perf_counter() - t0) / intra_iters
    del iout
    record["intra_device_realtime_x"] = round((intra_n / intra_dt) / fps, 3)
    print(json.dumps(record), flush=True)

    # ---- end-to-end wall clock in the PRODUCTION configuration:
    # decode -> device I+P chain ladder -> CABAC host entropy -> fMP4
    # packaging, through JaxBackend.run with decode prefetch and
    # one-batch-in-flight overlap. This is the north-star number
    # (BASELINE.md: wall-clock per video-minute vs the ~1.0x-realtime
    # NVENC ladder); the device-only figure above isolates the XLA
    # program. gop_mode/entropy come from config defaults (p + cabac).
    import shutil
    import tempfile

    from vlog_tpu.worker.pipeline import process_video

    e2e_h, e2e_w = 2160, 3840
    # one chain warms/compiles; two dispatches measure steady state
    warm_frames, e2e_frames = config.GOP_LEN, 48
    e2e_fps = 30

    def write_y4m(path, n_frames):
        with open(path, "wb") as fp:
            fp.write(f"YUV4MPEG2 W{e2e_w} H{e2e_h} F{e2e_fps}:1 Ip A1:1 "
                     "C420jpeg\n".encode())
            uv = rng.integers(0, 256,
                              (e2e_h // 2, e2e_w // 2)).astype(np.uint8)
            yy2, xx2 = np.mgrid[0:e2e_h, 0:e2e_w]
            ybase = ((yy2 // 8 + xx2 // 8) % 256).astype(np.int16)
            for i in range(n_frames):
                fp.write(b"FRAME\n")
                # shift the pattern per frame: realistic motion for the
                # chain's motion search, not a static all-skip scene
                yf = np.clip(np.roll(ybase, i, axis=1)
                             + rng.integers(-20, 20, ybase.shape),
                             0, 255).astype(np.uint8)
                fp.write(yf.tobytes())
                fp.write(uv.tobytes())
                fp.write(uv.tobytes())

    tmp = tempfile.mkdtemp(prefix="vlog-bench-")
    try:
        # Warm pass on ONE chain: compiles the 6-rung chain program (the
        # persistent compile cache keeps this across runs).
        warm_path = os.path.join(tmp, "warm.y4m")
        write_y4m(warm_path, warm_frames)
        process_video(warm_path, os.path.join(tmp, "warm"), audio=False)

        src_path = os.path.join(tmp, "src.y4m")
        write_y4m(src_path, e2e_frames)
        t0 = time.perf_counter()
        result = process_video(src_path, os.path.join(tmp, "run"),
                               audio=False)
        e2e_wall = time.perf_counter() - t0
        e2e_realtime = (e2e_frames / e2e_fps) / e2e_wall
        rung_count = len(result.run.rungs)
        stage_s = dict(getattr(result.run, "stage_s", {}) or {})
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    record.update({
        "compile_s": round(compile_seconds(), 3),   # now includes e2e
        "e2e_realtime_x": round(e2e_realtime, 4),
        "e2e_gop_mode": config.GOP_MODE,
        "e2e_entropy": config.H264_ENTROPY,
        "e2e_gop_len": result.run.gop_len,   # the chain length actually run
        "e2e_rungs": rung_count,
        "e2e_wall_s": round(e2e_wall, 2),
        "e2e_video_s": round(e2e_frames / e2e_fps, 2),
        "e2e_stage_s": stage_s,
    })
    print(json.dumps(record), flush=True)


# ---------------------------------------------------------------------------
# Entropy body: host CABAC throughput (always CPU — a host property)
# ---------------------------------------------------------------------------

def run_entropy() -> None:
    """Measure the threaded host entropy stage on REAL chain-program
    levels: run the 1080p-ladder chain DSP once on CPU (cheap enough),
    then time `H264Encoder.encode_chain` over the production 16-thread
    pool. Reported as macroblocks/s, projected onto the 4K 6-rung
    ladder's MB/frame so the orchestrator can derive a co-located e2e
    bound. MB/s is the right invariant: per-MB CABAC cost is dominated
    by coefficient coding and is resolution-independent at fixed QP."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from concurrent.futures import ThreadPoolExecutor

    from vlog_tpu import config
    from vlog_tpu.backends.base import plan_rung_geometry
    from vlog_tpu.codecs.h264.api import H264Encoder
    from vlog_tpu.codecs.h264.encoder import FrameLevels
    from vlog_tpu.parallel.ladder import ladder_chain_program

    src_h, src_w = 1080, 1920
    ladder = config.ladder_for_source(src_h)
    rungs = _ladder_rungs(plan_rung_geometry, ladder, src_h, src_w)
    clen = config.GOP_LEN
    rng = np.random.default_rng(0)

    fn, mats = ladder_chain_program(
        rungs, src_h, src_w, search=config.MOTION_SEARCH_RADIUS,
        deblock=config.H264_DEBLOCK)
    # Realistic-statistics content, NOT _structured_frames: that
    # generator's fully-random chroma planes cost ~0.5 MB/frame even at
    # QP 48 — no real video looks like that, and the rate controller
    # would never ship it at ladder bitrates. Smooth chroma + mild luma
    # noise lets the QP calibration below actually reach the ladder's
    # operating point.
    yy, xx = np.mgrid[0:src_h, 0:src_w]
    base = ((yy // 8 + xx // 8) % 256).astype(np.int16)
    y = np.stack([
        np.clip(np.roll(base, i, axis=1)
                + rng.integers(-6, 7, base.shape), 0, 255).astype(np.uint8)
        for i in range(clen)])
    cu = ((yy[::2, ::2] * 255) // src_h).astype(np.uint8)
    u = np.repeat(cu[None], clen, 0)
    v = np.repeat(255 - cu[None], clen, 0)

    i32 = lambda a: np.ascontiguousarray(a, np.int32)

    def stage(qps):
        """Chain DSP at ``qps`` -> per-rung entropy inputs + MB count."""
        outs = jax.block_until_ready(
            fn(y[None], u[None], v[None], mats, qps))
        per_rung = []   # (encoder, lv0, p_list, qarr, mbs_per_frame)
        total_mbs = 0
        for name, h, w, base_qp in rungs:
            ro = {k: np.asarray(outs[name][k]) for k in
                  ("i_luma_dc", "i_luma_ac", "i_chroma_dc",
                   "i_chroma_ac", "p_luma", "p_chroma_dc",
                   "p_chroma_ac", "mv")}
            qarr = qps[name][0]
            lv0 = FrameLevels(luma_dc=i32(ro["i_luma_dc"][0]),
                              luma_ac=i32(ro["i_luma_ac"][0]),
                              chroma_dc=i32(ro["i_chroma_dc"][0]),
                              chroma_ac=i32(ro["i_chroma_ac"][0]),
                              qp=int(qarr[0]))
            p_list = [{"luma": i32(ro["p_luma"][0, fi]),
                       "chroma_dc": i32(ro["p_chroma_dc"][0, fi]),
                       "chroma_ac": i32(ro["p_chroma_ac"][0, fi]),
                       "mv": i32(ro["mv"][0, fi])}
                      for fi in range(clen - 1)]
            enc = H264Encoder(width=w, height=h, fps_num=30, fps_den=1,
                              qp=base_qp, entropy=config.H264_ENTROPY,
                              deblock=config.H264_DEBLOCK)
            mbs = (-(-h // 16)) * (-(-w // 16))
            per_rung.append((enc, lv0, p_list, qarr, mbs))
            total_mbs += mbs * clen
        return per_rung, total_mbs

    # Per-MB CABAC cost scales with BITS per MB, so throughput must be
    # measured at the PRODUCTION operating point: total bytes/frame ~=
    # the ladder's bitrate sum (what the rate controller delivers), not
    # whatever the raw synthetic content costs at base QP (measured ~9x
    # hotter — that understated co-located throughput by the same
    # factor). Calibrate with the textbook bits-halve-per-6-QP slope.
    target_bpf = sum(r.video_bitrate for r in ladder) / 8.0 / 30.0
    qps = _chain_qps(np, rungs, clen)
    # one worker-count for the probe pool, the measurement pool, and
    # the per-vCPU normalization (C coders release the GIL: scaling is
    # by core, and the divisor must match what the pool can use)
    n_workers = max(1, min(16, os.cpu_count() or 1))
    import math as _math

    best = None          # (log-distance, per_rung, total_mbs, bpf)
    for _ in range(4):
        per_rung, total_mbs = stage(qps)
        with ThreadPoolExecutor(n_workers) as p0:
            probe = [enc.encode_chain(lv0, p_list, qarr, None, pool=p0)
                     for enc, lv0, p_list, qarr, _ in per_rung]
        bpf = sum(len(ef.avcc) for rung in probe
                  for ef in rung) / clen
        dist = abs(_math.log2(max(bpf, 1.0) / target_bpf))
        if best is None or dist < best[0]:
            best = (dist, per_rung, total_mbs, bpf)
        if dist < _math.log2(1.4):
            break
        # asymmetric step, same cliff lesson as the rate controller:
        # the downhill slope is far steeper than bits-halve-per-6-QP
        # (measured -10 QP => 26x at 1080p), so spend credit slowly
        delta = 6 * _math.log2(bpf / target_bpf)
        delta = int(round(delta if delta > 0 else max(delta / 3, -4)))
        nxt = {k: np.clip(q + delta, 10, 48) for k, q in qps.items()}
        if all(np.array_equal(nxt[k], qps[k]) for k in qps):
            break            # saturated at the clip bounds: no progress
        qps = nxt
    _, per_rung, total_mbs, _cal_bpf = best

    # Exactly the production shape: rungs serial, frames within a chain
    # parallel on the shared 16-thread pool (consume_chain's loop).
    pool = ThreadPoolExecutor(max_workers=n_workers)

    def code_all():
        return [enc.encode_chain(lv0, p_list, qarr, None, pool=pool)
                for enc, lv0, p_list, qarr, _ in per_rung]

    code_all()                                   # warm (table init etc.)
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        frames = code_all()
    dt = (time.perf_counter() - t0) / iters
    coded_bytes = sum(len(ef.avcc) for rung in frames for ef in rung)

    mb_per_s = total_mbs / dt
    # Project onto the 4K contractual ladder: MB/frame across all 6 rungs.
    mb_4k = sum((-(-p.height // 16)) * (-(-p.width // 16))
                for r in config.QUALITY_LADDER
                for p in [plan_rung_geometry(3840, 2160, r)])
    # bytes fields are RAW 1080p-ladder values (the measurement's own
    # operating point); only the fps field is projected to 4K MBs
    print(json.dumps({
        "entropy_mode": config.H264_ENTROPY,
        "entropy_threads": n_workers,
        "entropy_mb_per_s": round(mb_per_s, 0),
        # per-vCPU normalization: the C coders release the GIL and
        # frames are independent, so entropy scales ~linearly with host
        # cores — a production TPU host (100+ vCPUs) multiplies this
        "entropy_mb_per_s_per_vcpu": round(mb_per_s / n_workers, 0),
        "entropy_ladder_fps_1080p": round(clen / dt, 2),
        "entropy_ladder_fps_4k_equiv": round(mb_per_s / mb_4k, 2),
        "entropy_bytes_per_frame": round(coded_bytes / clen, 0),
        "entropy_target_bytes_per_frame": round(target_bpf, 0),
        # entropy scales ~linearly with host cores (per-frame slices are
        # independent); production TPU hosts carry an order of magnitude
        # more vCPUs than this dev VM
        "entropy_host_vcpus": os.cpu_count(),
    }), flush=True)


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------

def _json_line(stdout: str | None) -> str | None:
    for line in reversed((stdout or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            return line
    return None


def _child(mode: str, timeout_s: int, *, cpu: bool) -> str | None:
    """Run one body subprocess; returns its last JSON line, if any.

    ``cpu`` pins the child to XLA:CPU (the entropy body is a host
    measurement); the device body inherits the environment as it is, so
    an environment pinned to CPU fails it rather than being overridden.
    On timeout the partially-captured stdout is still scanned: the body
    prints the device record the moment that section completes, so a
    stalled e2e section no longer discards a finished measurement.
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu") if cpu else None
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), mode],
            env=env, timeout=timeout_s,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
    except subprocess.TimeoutExpired as exc:
        print(f"bench: {mode} timed out after {timeout_s}s",
              file=sys.stderr)
        out = exc.stdout
        if isinstance(out, bytes):
            out = out.decode(errors="replace")
        return _json_line(out)
    sys.stderr.write(proc.stderr[-2000:])
    if proc.returncode != 0:
        print(f"bench: {mode} rc={proc.returncode}", file=sys.stderr)
    # Scan stdout regardless of exit status: a crash after the device
    # section printed its record must not discard a finished
    # measurement. The platform-guard exit (rc=3) prints no JSON, so a
    # run that found no TPU yields None.
    return _json_line(proc.stdout)


def _merge_entropy(record: dict, entropy_line: str | None) -> dict:
    """Fold the entropy body's record in and derive the co-located e2e
    bound: device DSP and host entropy overlap in production (one batch
    in flight), so steady state = min(stage throughputs) at 30 fps."""
    if not entropy_line:
        return record
    try:
        ent = json.loads(entropy_line)
    except ValueError:
        return record
    record.update(ent)
    chain_fps = record.get("chain_fps")
    ent_fps = ent.get("entropy_ladder_fps_4k_equiv")
    if chain_fps and ent_fps:
        coloc = min(chain_fps, ent_fps) / 30.0
        record["coloc_e2e_estimate_x"] = round(coloc, 2)
        record["coloc_bound"] = ("entropy" if ent_fps < chain_fps
                                 else "device")
        record["coloc_vs_baseline"] = round(
            coloc / NVENC_FULL_LADDER_REALTIME, 2)
    return record


def _stamp_trend(record: dict) -> dict:
    """Annotate the outgoing record with the committed-trajectory trend
    (obs/benchtrend.py), so every bench round self-reports whether it
    regressed the series it is about to extend. Best-effort: a bench
    record must never be lost to a trend-gate parse error."""
    try:
        from vlog_tpu.obs.benchtrend import summary_line

        record["trend"] = summary_line(os.path.dirname(
            os.path.abspath(__file__)))
    except Exception as exc:   # noqa: BLE001 — stamp is garnish
        record["trend"] = f"trend unavailable: {exc}"
    return record


def main() -> int:
    if len(sys.argv) >= 2 and sys.argv[1] == "--body":
        run_body()
        return 0
    if len(sys.argv) >= 2 and sys.argv[1] == "--entropy":
        run_entropy()
        return 0

    # The one child that opens the chip. No record from it — no TPU, a
    # crash before the device section, a timeout with nothing printed —
    # means no record at all.
    line = _child("--body", TPU_TIMEOUT_S, cpu=False)
    if not line:
        print("bench: the device body produced no record; nothing to "
              "publish", file=sys.stderr)
        return 1
    # Host entropy throughput (CPU, accelerator-independent).
    entropy_line = _child("--entropy", CPU_TIMEOUT_S, cpu=True)
    print(json.dumps(_stamp_trend(_merge_entropy(
        json.loads(line), entropy_line))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
