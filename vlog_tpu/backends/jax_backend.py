"""The JAX/XLA ladder backend — decode once, emit every rung in one pass.

This is the ``device=tpu`` encoder the accelerator boundary selects,
replacing the reference's one-ffmpeg-process-per-rung scheme
(worker/transcoder.py:2528-2559 parallel batches; worker/hwaccel.py:647
command builder). Pipeline per frame batch:

  host decode (source.py) -> device: ladder resize (MXU matmuls,
  ops/resize.py) -> device: per-rung intra encode (encoder.encode_gop)
  -> host: CAVLC entropy + fMP4 packaging (threads, overlapped with the
  next batch's device work)

Segments are cut at whole-second boundaries (all frames are IDR-capable,
so any boundary is a valid CMAF chunk start). Output layout per rung:

    {out}/{rung}/init.mp4
    {out}/{rung}/segment_%05d.m4s
    {out}/{rung}/playlist.m3u8

matching what media.hls.dash_manifest expects and what the reference's
validate_hls_playlist checks (transcoder.py:816-947).

Resume: an interrupted run restarts at the first segment index any rung
is missing (quality_progress semantics, reference database.py:209-248) —
GOP-chunked execution keeps checkpoint granularity even though a single
XLA dispatch is not interruptible (SURVEY.md section 7 hard part #3).
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from pathlib import Path

import numpy as np

from vlog_tpu import config
from vlog_tpu.backends.base import (
    Capabilities,
    ExecutionPlan,
    PlannedRung,
    ProgressFn,
    RungResult,
    RunResult,
    plan_rung_geometry,
    register_backend,
)
from vlog_tpu.backends.rate_control import RateController
from vlog_tpu.backends.source import open_source
from vlog_tpu.codecs.h264.api import H264Encoder
from vlog_tpu.codecs.jpeg import encode_jpeg_yuv420
from vlog_tpu.media import hls
from vlog_tpu.media.fmp4 import Sample, TrackConfig, avc1_sample_entry, init_segment, media_segment
from vlog_tpu.media.probe import VideoInfo
from vlog_tpu.utils.fsio import atomic_write_bytes, atomic_write_text, prepare_init_segment
from vlog_tpu.ops.colorspace import yuv420_to_rgb
from vlog_tpu.ops.resize import resize_yuv420
from vlog_tpu.parallel.compile_cache import ensure_compile_cache


class JaxBackend:
    """Runs the one-pass ladder on whatever devices JAX exposes."""

    name = "jax"

    def detect(self) -> Capabilities:
        import jax

        ensure_compile_cache()

        devices = jax.devices()
        # memory_stats() is None where the runtime does not report it
        # (XLA:CPU)
        stats = devices[0].memory_stats() or {}
        return Capabilities(
            backend=self.name,
            device_kind=devices[0].platform,
            device_count=len(devices),
            codecs=("h264",),
            decode_codecs=("h264", "raw"),
            max_parallel_jobs=1,
            memory_bytes=stats.get("bytes_limit"),
            details={"devices": [str(d) for d in devices],
                     "jax_device_kind": devices[0].device_kind},
        )

    # ------------------------------------------------------------------
    def plan(self, source: VideoInfo, rungs=None, out_dir: Path | str = ".",
             **opts) -> ExecutionPlan:
        if rungs is None:
            rungs = config.ladder_for_source(source.height)
        planned = tuple(
            plan_rung_geometry(source.width, source.height, r) for r in rungs
        )
        codec = opts.get("codec", "h264")
        if codec == "hevc":
            codec = "h265"
        if codec in ("h265", "av1"):
            from dataclasses import replace

            planned = tuple(replace(r, codec=codec) for r in planned)
        elif codec != "h264":
            raise ValueError(f"unknown codec {codec!r}")
        from vlog_tpu.media.y4m import fps_to_fraction

        fps_num, fps_den = fps_to_fraction(source.fps or 30.0)
        seg_s = opts.get("segment_duration_s", config.SEGMENT_DURATION_S)
        fps = fps_num / fps_den
        frames_per_seg = max(1, round(seg_s * fps))
        gop_len = 1
        gop_mode = opts.get("gop_mode", config.GOP_MODE)
        if gop_mode == "p":
            # Pick the divisor of frames-per-segment closest to GOP_LEN
            # (segments must start on chain boundaries = IDRs). Divisors
            # somewhat above the target are allowed so awkward frame
            # rates (e.g. 25fps/1s segments) still get long chains.
            cap = min(frames_per_seg, 2 * config.GOP_LEN)
            divisors = [d for d in range(1, cap + 1)
                        if frames_per_seg % d == 0]
            gop_len = min(divisors,
                          key=lambda d: (abs(d - config.GOP_LEN), -d))
            if gop_len <= max(2, config.GOP_LEN // 3):
                import logging

                logging.getLogger("vlog_tpu.backend").warning(
                    "gop_mode=p degraded to %d-frame chains "
                    "(frames/segment=%d has no divisor near GOP_LEN=%d); "
                    "bitrate efficiency suffers — consider adjusting "
                    "VLOG_SEGMENT_DURATION", gop_len, frames_per_seg,
                    config.GOP_LEN)
        return ExecutionPlan(
            source=source,
            rungs=planned,
            out_dir=Path(out_dir),
            segment_duration_s=seg_s,
            frame_batch=opts.get("frame_batch", config.TPU_FRAME_BATCH),
            fps_num=fps_num,
            fps_den=fps_den,
            total_frames=source.frame_count,
            thumbnail=opts.get("thumbnail", True),
            gop_len=gop_len,
            streaming_format=opts.get("streaming_format",
                                      config.STREAMING_FORMAT),
        )

    # ------------------------------------------------------------------
    def run(self, plan: ExecutionPlan, progress_cb: ProgressFn | None = None,
            *, resume: bool = True) -> RunResult:
        from vlog_tpu.utils import failpoints

        failpoints.hit("backend.encode")    # chaos: simulated device fault
        ensure_compile_cache()
        # The host entropy stage is the C coders or nothing: with
        # VLOG_NATIVE unset, a library that did not build fails the run
        # here instead of entropy-coding in Python behind a healthy log.
        from vlog_tpu.native import require_lib

        require_lib()
        t0 = time.monotonic()
        if any(r.codec == "h265" for r in plan.rungs):
            from vlog_tpu.backends.hevc_path import run_hevc

            return run_hevc(self, plan, progress_cb, resume, t0)
        if any(r.codec == "av1" for r in plan.rungs):
            from vlog_tpu.backends.av1_path import run_av1

            return run_av1(self, plan, progress_cb, resume, t0)
        out = plan.out_dir
        out.mkdir(parents=True, exist_ok=True)

        fps = plan.fps_num / plan.fps_den
        frames_per_seg = max(1, round(plan.segment_duration_s * fps))
        timescale = plan.fps_num * 1000
        frame_dur = plan.fps_den * 1000
        # Legacy HLS: MPEG-TS segments with muxed audio, no init/DASH.
        ts_mode = plan.streaming_format == "hls_ts"
        seg_ext = "ts" if ts_mode else "m4s"

        encoders: dict[str, H264Encoder] = {}
        tracks: dict[str, TrackConfig] = {}
        seg_counts: dict[str, int] = {}
        seg_durs: dict[str, list[float]] = {}
        bytes_written: dict[str, int] = {}
        psnr_acc: dict[str, list[float]] = {}
        init_matched: dict[str, bool] = {}
        for rung in plan.rungs:
            # Chain mode runs the in-loop deblocking filter (the DSP and
            # the slice headers' idc must agree — ladder_chain_program
            # gets the same flag below); intra mode leaves it off.
            enc = H264Encoder(width=rung.width, height=rung.height,
                              fps_num=plan.fps_num, fps_den=plan.fps_den,
                              qp=rung.qp, entropy=config.H264_ENTROPY,
                              deblock=(config.H264_DEBLOCK
                                       and plan.gop_len > 1))
            encoders[rung.name] = enc
            tracks[rung.name] = TrackConfig(
                track_id=1, handler="vide", timescale=timescale,
                sample_entry=avc1_sample_entry(rung.width, rung.height,
                                               enc.avcc_config),
                width=rung.width, height=rung.height,
            )
            rdir = out / rung.name
            rdir.mkdir(parents=True, exist_ok=True)
            if not ts_mode:
                init_matched[rung.name] = prepare_init_segment(
                    rdir, init_segment(tracks[rung.name]),
                    config_tag=(f"h264:{config.H264_ENTROPY}"
                                f":deblock={int(enc.deblock)}"
                                f":gop={plan.gop_len}"))
            seg_counts[rung.name] = 0
            seg_durs[rung.name] = []
            bytes_written[rung.name] = 0
            psnr_acc[rung.name] = []

        # --- resume point: first segment index any rung is missing.
        # (TS mode restarts from 0: continuity counters span the whole
        # playlist, so a fresh muxer cannot append mid-stream.)
        src = open_source(plan.source.path)
        total = src.frame_count
        start_segment = 0
        # (any failure between here and the decode loop must not leak
        # the source — see the except below)
        # Foreign (libav) sources have keyframe-coarse seeking only, so
        # mid-stream segment resume would misalign frames: restart clean.
        try:
            return self._run_with_source(
                plan, progress_cb, resume, t0, src, total, out, fps,
                frames_per_seg, timescale, frame_dur, ts_mode, seg_ext,
                encoders, tracks, seg_counts, seg_durs, bytes_written,
                psnr_acc, init_matched)
        except BaseException:
            src.close()
            raise

    def _run_with_source(self, plan, progress_cb, resume, t0, src, total,
                         out, fps, frames_per_seg, timescale, frame_dur,
                         ts_mode, seg_ext, encoders, tracks, seg_counts,
                         seg_durs, bytes_written, psnr_acc,
                         init_matched) -> RunResult:
        # Resume CANDIDATE from the on-disk segment scan. The definitive
        # resume point is fixed below once the dispatch batch size is
        # known: byte-identical resume must land on a batch boundary the
        # rate-control journal can replay (backends/rc_journal.py), so
        # the candidate may be clamped down — or to zero (cold restart,
        # still deterministic) when the journal is missing or from a
        # differently-configured run.
        start_segment = 0
        resume_per_rung: dict[str, list[int]] | None = None
        if resume and not ts_mode and src.exact_seek:
            resume_per_rung = self._scan_resume_candidates(plan, out,
                                                           init_matched)
            start_segment = min(len(d) for d in resume_per_rung.values())
        start_frame = start_segment * frames_per_seg

        pending: dict[str, list[Sample]] = {r.name: [] for r in plan.rungs}
        frames_done = start_frame
        thumb_path = None

        # --- TS-mode segment writer state (muxers persist across
        # segments for playlist-wide continuity counters).
        from vlog_tpu.media.ts import TsMuxer, TsSample

        audio_by_rate = plan.audio_adts or {}
        ts_muxers: dict[str, TsMuxer] = {}
        ts_frame_idx = {r.name: start_frame for r in plan.rungs}
        ts_audio_idx = {r.name: 0 for r in plan.rungs}

        # Exact 90 kHz timestamps: multiply BEFORE dividing, per index —
        # a pre-truncated per-frame tick drifts A/V apart on non-integer
        # rates (23.976 fps / 44.1 kHz) by ~1 s/hour.
        def vpts(idx: int) -> int:
            return idx * 90000 * plan.fps_den // plan.fps_num

        def apts(idx: int, sr: int) -> int:
            return idx * 90000 * 1024 // sr

        def write_segment(rung: PlannedRung, chunk: list[Sample]) -> None:
            name = rung.name
            if not ts_mode:
                self._write_segment(out, rung, tracks[name], seg_counts,
                                    seg_durs, bytes_written, chunk,
                                    timescale)
                return
            audio = audio_by_rate.get(rung.audio_bitrate)
            mux = ts_muxers.get(name)
            if mux is None:
                mux = ts_muxers[name] = TsMuxer(has_video=True,
                                                has_audio=audio is not None)
            i0 = ts_frame_idx[name]
            vsamples = [TsSample(s.data, pts=vpts(i0 + k), is_idr=s.is_sync)
                        for k, s in enumerate(chunk)]
            ts_frame_idx[name] = i0 + len(chunk)
            asamples = []
            if audio is not None:
                frames, sr = audio
                t_end = vpts(ts_frame_idx[name])
                j = ts_audio_idx[name]
                while j < len(frames) and apts(j, sr) < t_end:
                    asamples.append(TsSample(frames[j], pts=apts(j, sr)))
                    j += 1
                ts_audio_idx[name] = j
            data = mux.mux_segment(video=vsamples, audio=asamples or None)
            idx = seg_counts[name]
            path = out / name / f"segment_{idx + 1:05d}.ts"
            atomic_write_bytes(path, data)
            seg_counts[name] = idx + 1
            seg_durs[name].append(sum(s.duration for s in chunk) / timescale)
            bytes_written[name] += len(data)

        # --- the one-pass ladder program: ONE dispatch per GOP batch
        # emits quantized levels for EVERY rung (SURVEY §2d.2); over >1
        # chip the ladder lays out as a 2-D (data × rung) grid — frames
        # shard the data axis, rung columns split the ladder — resolved
        # by grid_for_run() (slot submesh devices under the scheduler,
        # every visible device otherwise; VLOG_TPU_MESH picks the
        # shape). All batch math keys off the grid's DATA-axis width
        # only, so every shape whose data width divides the frame batch
        # stages identical batches — the cross-shape byte-identity
        # contract tests/test_mesh_equivalence.py asserts.
        import jax

        from vlog_tpu.parallel.ladder import (ladder_chain_grid,
                                              ladder_encode_grid)
        from vlog_tpu.parallel.scheduler import (grid_for_run,
                                                 host_pool_for_run)

        src_h, src_w = plan.source.height, plan.source.width
        rungs_spec = tuple((r.name, r.height, r.width, r.qp)
                           for r in plan.rungs)
        chain_mode = plan.gop_len > 1
        if chain_mode:
            # Chains are independent mini-GOPs, so the grid shards the
            # chain axis; enough chains per dispatch to honor frame_batch
            # (amortizing host overhead), rounded to the data-axis width
            # (NOT the device count: a 2x4 grid pads a small batch to 2
            # chains where the 1-D mesh padded it to 8).
            clen = plan.gop_len
            hint = max(1, -(-plan.frame_batch // clen))
            grid = grid_for_run(rungs_spec, batch_hint=hint)
            prog = ladder_chain_grid(
                rungs_spec, src_h, src_w,
                search=config.MOTION_SEARCH_RADIUS, grid=grid,
                deblock=config.H264_DEBLOCK)
            chains_per = max(prog.data, hint + (-hint) % prog.data)
            batch_n = clen * chains_per
        else:
            grid = grid_for_run(rungs_spec, batch_hint=plan.frame_batch)
            prog = ladder_encode_grid(rungs_spec, src_h, src_w, grid)
            # Fixed staged batch size (single compile; data-divisible).
            batch_n = max(plan.frame_batch, prog.data)
            batch_n += (-batch_n) % prog.data

        # Closed-loop VBR toward each rung's ladder bitrate.
        controllers = {
            r.name: RateController(target_bps=r.video_bitrate, fps=fps,
                                   init_qp=r.qp)
            for r in plan.rungs
        }
        npix = {r.name: r.height * r.width for r in plan.rungs}

        # Stage accounting: decode_wait = blocked on the prefetch fifo;
        # compute_wait = block_until_ready on the dispatch outputs (pure
        # device compute, since dispatch is async); device_pull =
        # np.asarray AFTER readiness (pure d2h transfer — without the
        # split, the pull absorbed the XLA compute and the profile
        # could not distinguish the two, VERDICT r4 weak #3); entropy =
        # host slice coding; package = segment mux + fsync. All five are
        # cumulative BUSY seconds per stage; the executor adds the
        # overlap gauges (pipeline_depth / max_in_flight / host_busy_s /
        # host_wall_s / host_occupancy) on top.
        prof = {"decode_wait_s": 0.0, "compute_wait_s": 0.0,
                "device_pull_s": 0.0, "entropy_s": 0.0, "package_s": 0.0}

        def dispatch(by, bu, bv):
            n_real = by.shape[0]
            if n_real < batch_n:   # tail: replicate last frame, drop later
                reps = batch_n - n_real
                by = np.concatenate([by, np.repeat(by[-1:], reps, axis=0)])
                bu = np.concatenate([bu, np.repeat(bu[-1:], reps, axis=0)])
                bv = np.concatenate([bv, np.repeat(bv[-1:], reps, axis=0)])
            pipe.note_pad_waste(n_real, batch_n)
            if chain_mode:
                chain = lambda p: p.reshape((chains_per, clen) + p.shape[1:])
                by, bu, bv = chain(by), chain(bu), chain(bv)
                # I frames carry the whole chain as its reference: spend
                # ~2 QP more on them than on the P frames they anchor
                # (standard I/P offset; the rate controller sees the
                # blended chain bytes either way).
                qps = {}
                for r in plan.rungs:
                    # fractional working point -> per-frame dither
                    q = controllers[r.name].frame_qps(
                        chains_per * clen).reshape(chains_per, clen)
                    q[:, 0] = np.maximum(q[:, 0] - 2, 0)
                    qps[r.name] = q
                # per-rung device RC params; zero-target rungs keep
                # alpha 0 (calibrate_proxy no-ops), disabling adjustment
                rc = {r.name: controllers[r.name].device_rc_params()
                      for r in plan.rungs}
                # the grid stages per column (frames replicated along
                # the rung axis, each rung's QP/RC routed to its owning
                # column) and leaves each rung's outputs on that column
                return prog.dispatch(by, bu, bv, qps, rc), n_real, qps
            qps = {r.name: controllers[r.name].frame_qps(batch_n)
                   for r in plan.rungs}
            return prog.dispatch(by, bu, bv, qps), n_real, qps

        # --- stage-decoupled consume side (parallel/executor.py): rungs
        # pull + entropy-code concurrently on per-rung ordered threads,
        # frame-level work fans onto one shared cpu-count-sized pool,
        # and up to VLOG_PIPELINE_DEPTH batches are in flight.
        from vlog_tpu.parallel.executor import (LaggedRateControl,
                                                PipelineExecutor)

        rungs_by_name = {r.name: r for r in plan.rungs}
        rc = LaggedRateControl(controllers)

        # --- definitive resume point + rate-control journal. The scan
        # candidate is clamped to a segment boundary that is ALSO a
        # dispatch-batch boundary with a complete journal prefix; the
        # journal then replays the original run's rate-control schedule
        # so the resumed segments encode byte-identically (the
        # cross-worker hand-off contract — a successor must continue
        # the tree the uploaded digests already describe).
        from vlog_tpu.backends import rc_journal as rcj

        journal = None
        depth = config.PIPELINE_DEPTH
        start_batch = 0
        if not ts_mode:
            jpath = out / rcj.RC_JOURNAL_NAME
            header = rcj.make_header(
                batch_n=batch_n, depth=depth,
                frames_per_seg=frames_per_seg, gop_len=plan.gop_len,
                rungs=[r.name for r in plan.rungs],
                tag=(f"h264:{config.H264_ENTROPY}"
                     f":deblock={int(config.H264_DEBLOCK and plan.gop_len > 1)}"))
            if start_segment > 0:
                loaded = rcj.load_journal(jpath)
                entries = (loaded[1] if loaded is not None
                           and loaded[0] == header else {})
                a_seg, a_batch = rcj.aligned_resume_point(
                    start_segment, frames_per_seg=frames_per_seg,
                    batch_n=batch_n, entries=entries,
                    rungs=header["rungs"])
                if a_batch > 0:
                    # byte-identical resume: replay the journal so the
                    # controllers continue the original timeline
                    start_segment, start_batch = a_seg, a_batch
                    rc.replay(entries, start_batch, header["depth"])
                else:
                    # no replayable aligned point (journal missing, or
                    # batch padding outruns the tree): legacy resume —
                    # completed segments still skip re-encoding, but the
                    # controllers start cold, so the remaining segments
                    # are valid-not-identical. The journal is stamped
                    # with the resumed frame origin so a later run can
                    # never mistake it for the original timeline.
                    header = {**header,
                              "origin_frame": start_segment * frames_per_seg}
                self._apply_resume_state(
                    plan, resume_per_rung, start_segment, timescale,
                    seg_counts, seg_durs, bytes_written)
            journal = rcj.RCJournal(jpath, header, keep_batches=start_batch)
            start_frame = start_segment * frames_per_seg
            frames_done = start_frame
        if plan.thumbnail and start_segment > 0 \
                and (out / "thumbnail.jpg").exists():
            # resumed run: keep the original first-batch thumbnail — a
            # mid-stream frame would break tree byte-identity
            thumb_path = str(out / "thumbnail.jpg")

        def wait_device(batch):
            jax.block_until_ready(batch.outs)   # device compute, all rungs

        def pull_chain(name, batch):
            ro = batch.outs[name]
            return {k: np.asarray(ro[k]) for k in
                    ("i_luma_dc", "i_luma_ac", "i_chroma_dc",
                     "i_chroma_ac", "p_luma", "p_chroma_dc",
                     "p_chroma_ac", "mv", "sse_y", "qp_eff", "cost")}

        def process_chain(name, batch, host):
            """Entropy-code one rung of one dispatch of I+P chains
            (display order is chain-major, matching how frames were
            batched)."""
            from vlog_tpu.codecs.h264.encoder import FrameLevels

            i32 = lambda a: np.ascontiguousarray(a, np.int32)
            rung = rungs_by_name[name]
            n_real = batch.n_real
            te = time.perf_counter()
            sse = host["sse_y"]                       # (nc, clen)
            # the QPs the device ACTUALLY encoded at (plan + in-chain
            # adjustment) — slice headers must signal these
            qarr = host["qp_eff"]                     # (nc, clen)
            cost = host["cost"]                       # (nc, clen)
            batch_bytes = 0
            n_frames = 0
            cost_sum = 0.0
            rc_qs = []   # P-frame dither values: the working-point
            #              mix the controller must attribute to (the
            #              I frames carry the -2 anchor, excluded)
            plan_q = np.asarray(batch.qps[name])      # (nc, clen)
            for ci in range(chains_per):
                base = ci * clen
                if base >= n_real:
                    break
                keep = min(clen, n_real - base)
                # attribute to the PLAN (outer-loop) working point,
                # not qp_eff: the device's in-chain bumps are the
                # inner loop of a cascade — if the host attributed
                # to the realized QPs, its own corrective step would
                # cancel against the attribution shift and the plan
                # would never converge (measured: stuck 28% under)
                rc_qs.append(plan_q[ci, 1:keep])
                cost_sum += float(cost[ci, :keep].sum())
                lv0 = FrameLevels(
                    luma_dc=i32(host["i_luma_dc"][ci]),
                    luma_ac=i32(host["i_luma_ac"][ci]),
                    chroma_dc=i32(host["i_chroma_dc"][ci]),
                    chroma_ac=i32(host["i_chroma_ac"][ci]),
                    qp=int(qarr[ci, 0]))
                p_list = [
                    {"luma": i32(host["p_luma"][ci, fi]),
                     "chroma_dc": i32(host["p_chroma_dc"][ci, fi]),
                     "chroma_ac": i32(host["p_chroma_ac"][ci, fi]),
                     "mv": i32(host["mv"][ci, fi])}
                    for fi in range(keep - 1)
                ]
                mse = np.maximum(sse[ci, :keep] / npix[name], 1e-12)
                psnrs = np.where(mse < 1e-9, 99.0,
                                 10 * np.log10(255 ** 2 / mse))
                efs = encoders[name].encode_chain(
                    lv0, p_list, qarr[ci, :keep], psnrs,
                    pool=pipe.host_pool)
                for ef in efs:
                    pending[name].append(
                        Sample(data=ef.annexb if ts_mode else ef.avcc,
                               duration=frame_dur, is_sync=ef.is_idr))
                    psnr_acc[name].append(ef.psnr_y)
                    batch_bytes += len(ef.avcc)
                n_frames += keep
            rc_mix = (np.concatenate(rc_qs) if rc_qs else None)
            if rc_mix is not None and rc_mix.size == 0:
                rc_mix = None
            # posted here, applied in batch order on the dispatch
            # thread (observe + the device-RC bytes-per-proxy
            # calibration) — see LaggedRateControl
            rc.post(name, batch.index, nbytes=batch_bytes,
                    frames=max(n_frames, 1), frame_qps=rc_mix,
                    cost=cost_sum)
            if journal is not None:
                journal.record(batch.index, name, nbytes=batch_bytes,
                               frames=max(n_frames, 1), qps=rc_mix,
                               cost=cost_sum)
            pipe.prof_add("entropy_s", time.perf_counter() - te)
            tw = time.perf_counter()
            while len(pending[name]) >= frames_per_seg:
                chunk = pending[name][:frames_per_seg]
                pending[name] = pending[name][frames_per_seg:]
                write_segment(rung, chunk)
            pipe.prof_add("package_s", time.perf_counter() - tw)

        def pull_intra(name, batch):
            ro = batch.outs[name]
            n_real = batch.n_real
            # device ships int16 (halves the transfer); the CAVLC
            # coders (C + Python) work on int32
            levels = {
                k: np.ascontiguousarray(np.asarray(ro[k])[:n_real],
                                        np.int32)
                for k in ("luma_dc", "luma_ac", "chroma_dc", "chroma_ac")}
            sse = np.asarray(ro["sse_y"])[:n_real]
            return levels, sse

        def process_intra(name, batch, host):
            levels, sse = host
            rung = rungs_by_name[name]
            n_real = batch.n_real
            te = time.perf_counter()
            mse = np.maximum(sse / npix[name], 1e-12)
            psnrs = np.where(mse < 1e-9, 99.0,
                             10 * np.log10(255 ** 2 / mse))
            q_used = np.asarray(batch.qps[name])[:n_real]
            frames = encoders[name].encode_levels(levels, q_used, psnrs,
                                                  pool=pipe.host_pool)
            batch_bytes = 0
            for ef in frames:
                pending[name].append(
                    Sample(data=ef.annexb if ts_mode else ef.avcc,
                           duration=frame_dur, is_sync=ef.is_idr))
                psnr_acc[name].append(ef.psnr_y)
                batch_bytes += len(ef.avcc)
            rc.post(name, batch.index, nbytes=batch_bytes, frames=n_real,
                    frame_qps=q_used)
            if journal is not None:
                journal.record(batch.index, name, nbytes=batch_bytes,
                               frames=n_real, qps=q_used, cost=None)
            pipe.prof_add("entropy_s", time.perf_counter() - te)
            tw = time.perf_counter()
            while len(pending[name]) >= frames_per_seg:
                chunk = pending[name][:frames_per_seg]
                pending[name] = pending[name][frames_per_seg:]
                write_segment(rung, chunk)
            pipe.prof_add("package_s", time.perf_counter() - tw)

        def on_batch_done(batch):
            # serialized + batch-ordered by the executor's contract
            nonlocal frames_done
            frames_done += batch.n_real
            if progress_cb:
                # total is an estimate for foreign sources; never report
                # done > total
                t = max(total, frames_done)
                progress_cb(frames_done, t,
                            f"encoded {frames_done}/{t} frames")

        pipe = PipelineExecutor(
            [r.name for r in plan.rungs],
            pull=pull_chain if chain_mode else pull_intra,
            process=process_chain if chain_mode else process_intra,
            ready=wait_device, on_batch_done=on_batch_done,
            host_pool=host_pool_for_run(),   # shared across slot executors
            prof=prof, name="vlog-pipe")

        # Decode prefetch: a producer thread reads/decodes the NEXT batches
        # while the device computes and the host entropy-codes — the
        # decode ∥ transfer ∥ compute ∥ package overlap SURVEY §7 hard
        # part 5 calls mandatory at 4K rates. Bounded queue so decode can
        # run at most 2 batches ahead of the device.
        eof = object()
        fifo: queue_mod.Queue = queue_mod.Queue(maxsize=2)
        stop_decode = threading.Event()

        def producer() -> None:
            try:
                for item in src.read_batches(batch_n, start_frame):
                    while not stop_decode.is_set():
                        try:
                            fifo.put(item, timeout=0.5)
                            break
                        except queue_mod.Full:
                            continue
                    if stop_decode.is_set():
                        return
                fifo.put(eof)
            except BaseException as exc:  # noqa: BLE001 — relayed to consumer
                fifo.put(exc)

        decode_thread = threading.Thread(target=producer, daemon=True,
                                         name="vlog-decode-prefetch")
        decode_thread.start()

        batch_idx = 0
        try:
            while True:
                td = time.perf_counter()
                item = fifo.get()
                prof["decode_wait_s"] += time.perf_counter() - td
                if item is eof:
                    break
                if isinstance(item, BaseException):
                    raise item
                by, bu, bv = item
                # Thumbnail from the first batch (reference grabs an early
                # frame, transcoder.py:2247) — a 4K JPEG encode, so it
                # rides the executor's host pool, not the dispatch thread.
                if plan.thumbnail and thumb_path is None:
                    thumb_path = str(out / "thumbnail.jpg")
                    pipe.submit_aux(self._write_thumbnail, by[0], bu[0],
                                    bv[0], thumb_path)
                # Backpressure BEFORE planning: with a free slot secured,
                # batches <= N-depth are fully consumed, so applying
                # their observations here gives every depth (and every
                # thread interleaving) the same deterministic QP plan.
                pipe.reserve()
                rc.apply_upto(batch_idx - pipe.depth)
                outs, n_real, qps = dispatch(by, bu, bv)
                pipe.submit(outs, n_real, qps)
                batch_idx += 1
                if rc.hunting():
                    # Calibration/cliff hunt: drain the window to depth 0
                    # and apply every correction before the next batch is
                    # staged — with batches in flight each QP move lags
                    # extra batches, multiplying any overshoot burn.
                    pipe.drain()
                    rc.apply_upto(batch_idx - 1)
            pipe.drain()
            # Flush trailing partial segments.
            for rung in plan.rungs:
                if pending[rung.name]:
                    write_segment(rung, pending[rung.name])
                    pending[rung.name] = []
        finally:
            stop_decode.set()
            while True:     # unblock a producer stuck on a full queue
                try:
                    fifo.get_nowait()
                except queue_mod.Empty:
                    break
            decode_thread.join(timeout=10)
            pipe.close()
            src.close()
            if journal is not None:
                journal.close()

        # Inexact (libav) sources: the container's frame count is an
        # estimate — trust the frames actually decoded.
        true_total = total if src.exact_seek else frames_done
        duration_s = true_total / fps if fps else 0.0
        results = []
        variants = []
        for rung in plan.rungs:
            name = rung.name
            enc = encoders[name]
            playlist = hls.media_playlist(
                [hls.SegmentRef(uri=f"segment_{i + 1:05d}.{seg_ext}",
                                duration_s=seg_durs[name][i])
                 for i in range(seg_counts[name])],
                target_duration_s=plan.segment_duration_s,
                init_uri=None if ts_mode else "init.mp4",
            )
            ppath = out / name / "playlist.m3u8"
            atomic_write_text(ppath, playlist)
            total_dur = sum(seg_durs[name])
            achieved = int(bytes_written[name] * 8 / total_dur) if total_dur else 0
            results.append(RungResult(
                name=name, width=rung.width, height=rung.height,
                codec_string=enc.codec_string,
                segment_count=seg_counts[name],
                bytes_written=bytes_written[name],
                mean_psnr_y=float(np.mean(psnr_acc[name])) if psnr_acc[name] else None,
                achieved_bitrate=achieved,
                playlist_path=str(ppath),
                target_bitrate=rung.video_bitrate,
            ))
            # TS variants carry muxed AAC: CODECS must list every format
            # present (RFC 8216) and BANDWIDTH must include the audio.
            muxed = ts_mode and rung.audio_bitrate in audio_by_rate
            variants.append(hls.VariantRef(
                name=name, uri=f"{name}/playlist.m3u8",
                bandwidth=max(achieved, 1)
                + (rung.audio_bitrate if muxed else 0),
                width=rung.width,
                height=rung.height,
                codecs=(enc.codec_string + ",mp4a.40.2" if muxed
                        else enc.codec_string),
                frame_rate=fps,
                audio_group=("" if ts_mode else
                             (f"aud{rung.audio_bitrate // 1000}"
                              if rung.audio_bitrate else "")),
            ))
        atomic_write_text(out / "master.m3u8", hls.master_playlist(variants))
        if not ts_mode:      # DASH is CMAF-only; legacy TS serves HLS alone
            atomic_write_text(out / "manifest.mpd", hls.dash_manifest(
                variants, duration_s=duration_s,
                segment_duration_s=plan.segment_duration_s))

        return RunResult(
            rungs=results, frames_processed=frames_done,
            duration_s=duration_s, thumbnail_path=thumb_path,
            wall_s=time.monotonic() - t0,
            variants=variants, fps=fps,
            segment_duration_s=plan.segment_duration_s,
            stage_s={k: round(v, 3) for k, v in prof.items()}
            | pipe.gauges(),
            gop_len=plan.gop_len,
            resumed_segments=start_segment * len(plan.rungs),
            mesh_shape=prog.label,
        )

    # ------------------------------------------------------------------
    def _resume_scan(self, plan, out, timescale, seg_counts, seg_durs,
                     bytes_written, init_matched) -> int:
        """Reconstruct per-rung segment state from disk; returns the
        first segment index every rung still needs (shared by the H.264
        and HEVC paths — both emit the same CMAF tree).

        ``init_matched``: rung name -> True when the init segment on
        disk before this run matched the one this run writes. Segments
        from a run with a different init (entropy mode, QP base, SPS
        shape changed between runs) cannot be appended to — they
        reference another PPS — so such rungs restart from segment 0."""
        per_rung = self._scan_resume_candidates(plan, out, init_matched)
        start_segment = min(len(d) for d in per_rung.values())
        self._apply_resume_state(plan, per_rung, start_segment, timescale,
                                 seg_counts, seg_durs, bytes_written)
        return start_segment

    def _scan_resume_candidates(self, plan, out, init_matched
                                ) -> dict[str, list[int]]:
        """Per-rung timescale durations of the contiguous valid segments
        on disk (the scan half of :meth:`_resume_scan`; the H.264 path
        applies state separately so the resume point can first be
        clamped to a journal-replayable batch boundary)."""
        per_rung = {}
        for r in plan.rungs:
            existing = self._existing_segments(out / r.name)
            if existing and not init_matched.get(r.name, False):
                existing = []
            per_rung[r.name] = existing
        return per_rung

    @staticmethod
    def _apply_resume_state(plan, per_rung, start_segment, timescale,
                            seg_counts, seg_durs, bytes_written) -> None:
        """Install the resumed prefix into the run's per-rung state."""
        for rung in plan.rungs:
            durs = per_rung[rung.name][:start_segment]
            seg_counts[rung.name] = start_segment
            seg_durs[rung.name] = [d / timescale for d in durs]
            for i in range(start_segment):
                seg = plan.out_dir / rung.name / f"segment_{i + 1:05d}.m4s"
                bytes_written[rung.name] += seg.stat().st_size

    @staticmethod
    def _existing_segments(rdir: Path) -> list[int]:
        """Timescale durations of contiguous valid segments (resume state).

        A segment counts only if its moof parses and carries samples —
        the on-disk-validation analog of validate_hls_playlist's fMP4
        ``moof`` check (reference transcoder.py:930-941).
        """
        from vlog_tpu.media.boxes import parse_box_tree

        durations: list[int] = []
        if not (rdir / "init.mp4").exists():
            return durations
        i = 0
        while True:
            seg = rdir / f"segment_{i + 1:05d}.m4s"
            if not seg.exists() or seg.stat().st_size < 16:
                break
            try:
                with open(seg, "rb") as fp:
                    tree = parse_box_tree(fp)
                moof = next(b for b in tree if b.type == "moof")
                trun = moof.find("traf", "trun")
                n = int.from_bytes(trun.payload[4:8], "big")
                if n == 0:
                    break
                # trun payload: ver/flags, count, data_offset, then
                # (duration, size, flags, cts) per sample
                dur = sum(
                    int.from_bytes(trun.payload[12 + 16 * k:16 + 16 * k], "big")
                    for k in range(n)
                )
            except (StopIteration, AttributeError, ValueError, IndexError):
                break  # torn write
            durations.append(dur)
            i += 1
        return durations

    def _write_segment(self, out, rung: PlannedRung, track: TrackConfig,
                       seg_counts, seg_durs, bytes_written,
                       samples: list[Sample], timescale: int) -> None:
        name = rung.name
        idx = seg_counts[name]
        # base decode time = sum of durations of all prior segments
        base_time = int(round(sum(seg_durs[name]) * timescale))
        data = media_segment(track, idx + 1, base_time, samples)
        path = out / name / f"segment_{idx + 1:05d}.m4s"
        tmp = path.with_suffix(".m4s.tmp")
        tmp.write_bytes(data)
        tmp.rename(path)           # atomic publish (sprite_generator parity)
        seg_counts[name] = idx + 1
        seg_durs[name].append(sum(s.duration for s in samples) / timescale)
        bytes_written[name] += len(data)

    @staticmethod
    def _write_thumbnail(y, u, v, path: str, max_width: int = 1280) -> None:
        h, w = y.shape
        if w > max_width:
            th = max(2, round(h * max_width / w / 2) * 2)
            y, u, v = resize_yuv420(y[None], u[None], v[None], th, max_width)
            y, u, v = np.asarray(y[0]), np.asarray(u[0]), np.asarray(v[0])
        rgb = np.asarray(yuv420_to_rgb(y, u, v, standard="bt709"))
        from vlog_tpu.codecs.jpeg import encode_jpeg_rgb

        atomic_write_bytes(Path(path), encode_jpeg_rgb(
            (rgb * 255).astype(np.uint8), quality=85))


register_backend("jax", JaxBackend)
