"""Accelerator boundary: backend protocol, capability model, registry.

This is the keystone seam of the framework — the analog of the
reference's ``worker/hwaccel.py`` (detect_gpu_capabilities:412,
select_encoder:454, build_transcode_command:647). Where the reference
maps (codec, resolution) to an ffmpeg command line for NVENC/VAAPI/CPU,
here a :class:`Backend` maps a source + ladder to an executable plan and
runs it. Registering a new accelerator is one ``register_backend`` call;
the worker runtime, job gating, and APIs never import a concrete backend.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Protocol

from vlog_tpu import config
from vlog_tpu.media.probe import VideoInfo


@dataclass(frozen=True)
class Capabilities:
    """What an accelerator can do (reference: GPUCapabilities hwaccel.py:67
    + get_worker_capabilities:1050)."""

    backend: str                       # registry name, e.g. "jax"
    device_kind: str                   # "tpu" | "cpu" | "gpu"
    device_count: int
    codecs: tuple[str, ...]            # encodeable codecs
    decode_codecs: tuple[str, ...]     # decodeable codecs
    max_parallel_jobs: int = 1
    memory_bytes: int | None = None
    details: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "backend": self.backend,
            "device_kind": self.device_kind,
            "device_count": self.device_count,
            "codecs": list(self.codecs),
            "decode_codecs": list(self.decode_codecs),
            "max_parallel_jobs": self.max_parallel_jobs,
            "memory_bytes": self.memory_bytes,
            **self.details,
        }


@dataclass(frozen=True)
class PlannedRung:
    """One ladder rung with resolved output geometry."""

    name: str
    width: int
    height: int
    video_bitrate: int
    qp: int
    codec: str = "h264"
    audio_bitrate: int = 0     # paired AAC rendition rate (0 = video-only)


@dataclass
class ExecutionPlan:
    """Everything the backend needs to run one transcode job.

    The analog of the ffmpeg command lines built by
    build_cmaf_transcode_command (hwaccel.py:732) — but as data, so it can
    be inspected, checkpointed, and resumed.
    """

    source: VideoInfo
    rungs: tuple[PlannedRung, ...]
    out_dir: Path
    segment_duration_s: float = 6.0
    frame_batch: int = 8
    fps_num: int = 30
    fps_den: int = 1
    total_frames: int = 0
    streaming_format: str = "cmaf"     # "cmaf" (fMP4) for now
    thumbnail: bool = True
    # I+P chain length; 1 = all-intra. Always divides frames-per-segment
    # so every CMAF segment starts on an IDR.
    gop_len: int = 1
    # hls_ts mode: {audio_bitrate: (list_of_adts_frames, sample_rate)} —
    # classic HLS muxes audio INTO each variant's TS segments, so the
    # pipeline pre-encodes ADTS and the backend interleaves per segment.
    audio_adts: dict | None = None


@dataclass
class RungResult:
    name: str
    width: int
    height: int
    codec_string: str
    segment_count: int
    bytes_written: int
    # None = not measured this run (e.g. fully-resumed run encoded nothing),
    # never a fabricated 0.0.
    mean_psnr_y: float | None
    achieved_bitrate: int
    playlist_path: str
    target_bitrate: int = 0      # the ladder's ask; 0 = constant-QP run


@dataclass
class RunResult:
    rungs: list[RungResult]
    frames_processed: int
    duration_s: float
    thumbnail_path: str | None = None
    wall_s: float = 0.0
    # master-playlist variant refs (media.hls.VariantRef) so the pipeline
    # can re-emit manifests once audio renditions exist
    variants: list = field(default_factory=list)
    fps: float = 0.0
    segment_duration_s: float = 0.0
    # wall-clock accounting per pipeline stage (decode_wait_s /
    # compute_wait_s / device_pull_s / entropy_s / package_s): where the
    # e2e time went, so benches can report which stage bounds
    # throughput. compute_wait = block_until_ready on the async
    # dispatch (pure device compute); device_pull = np.asarray AFTER
    # readiness (pure device->host transfer). Each field is cumulative
    # BUSY seconds for its stage; since the stage-decoupled executor
    # (parallel/executor.py) runs rungs concurrently, busy sums can
    # exceed wall clock — the overlap gauges it adds (pipeline_depth,
    # max_in_flight, host_busy_s, host_wall_s, host_occupancy) say how
    # much actually overlapped.
    stage_s: dict = field(default_factory=dict)
    # chain length the run actually used (plan_for's segment-divisor
    # logic may pick a different value than config.GOP_LEN; 1 = intra)
    gop_len: int = 1
    # segments (summed across rungs) this run accepted from disk via
    # digest/structure-verified resume instead of re-encoding — the
    # bounded-loss accounting preemption-tolerant workers assert on
    # (vlog_resume_segments_skipped_total)
    resumed_segments: int = 0
    # the (data x rung) grid label the ladder program dispatched over
    # ("1x1" single chip); "" where a codec path does not report it
    mesh_shape: str = ""


# progress_cb(frames_done, frames_total, message)
ProgressFn = Callable[[int, int, str], None]


class Backend(Protocol):
    """Accelerator backend protocol (hwaccel.py:412-839 analog)."""

    name: str

    def detect(self) -> Capabilities: ...

    def plan(self, source: VideoInfo, rungs, out_dir: Path, **opts) -> ExecutionPlan: ...

    def run(self, plan: ExecutionPlan, progress_cb: ProgressFn | None = None,
            *, resume: bool = True) -> RunResult: ...


# --------------------------------------------------------------------------
# Registry (hwaccel.py:454 select_encoder analog)
# --------------------------------------------------------------------------

_REGISTRY: dict[str, Callable[[], Backend]] = {}


def register_backend(name: str, factory: Callable[[], Backend]) -> None:
    _REGISTRY[name] = factory


def available_backends() -> list[str]:
    return list(_REGISTRY)


def get_backend(name: str) -> Backend:
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None
    return factory()


_SELECTED: Backend | None = None


def select_backend(preference: str | None = None) -> Backend:
    """Pick the best available backend.

    Preference order mirrors the reference's GPU-over-CPU encoder
    selection (hwaccel.py:454-481): explicit preference, then whichever
    registered backend reports TPU devices, then anything. The choice is
    cached per process — probing instantiates backends (and may open
    accelerators), which must happen once, not per job.

    A backend whose ``detect()`` raises is skipped only while another
    registered backend answers; when none does, the last error
    propagates — the caller asked for an accelerator and must hear why
    there is none, not get a backend that never detected anything.
    """
    global _SELECTED
    if preference:
        return get_backend(preference)
    if _SELECTED is not None:
        return _SELECTED
    if not _REGISTRY:
        raise RuntimeError("no backends registered")
    best = None
    error: Exception | None = None
    for name in _REGISTRY:
        b = get_backend(name)
        try:
            caps = b.detect()
        except Exception as exc:  # noqa: BLE001 — re-raised below unless
            error = exc           # another backend answers
            continue
        if caps.device_kind == "tpu":
            _SELECTED = b
            return b
        if best is None:
            best = b
    if best is None:
        # registry non-empty and nothing answered: every detect() raised
        raise error
    _SELECTED = best
    return best


def require_accelerator(backend: Backend, accelerator: str) -> Capabilities:
    """What ``backend`` found, once it matches what the worker is about
    to advertise. A worker registered as ``tpu`` claims TPU-gated jobs;
    on a backend that resolved to anything else those jobs would run on
    XLA:CPU and complete, so the entry points refuse to start instead."""
    caps = backend.detect()
    if accelerator == "tpu" and caps.device_kind != "tpu":
        raise SystemExit(
            f"refusing to start: --accelerator tpu but backend "
            f"{caps.backend!r} found {caps.device_count} "
            f"{caps.device_kind!r} device(s) and no TPU; start with "
            "--accelerator cpu to run without one")
    return caps


def plan_rung_geometry(src_w: int, src_h: int, rung: config.QualityRung,
                      codec: str = "h264") -> PlannedRung:
    """Resolve output geometry for one rung: height from the ladder, width
    follows the source aspect ratio, rounded to even (mod-2, as the
    reference's scale filters do)."""
    h = min(rung.height, src_h if src_h % 2 == 0 else src_h - 1)
    h = h - (h % 2)
    w = round(src_w * h / src_h / 2) * 2 if src_h else h * 16 // 9
    return PlannedRung(
        name=rung.name, width=max(w, 2), height=max(h, 2),
        video_bitrate=rung.video_bitrate, qp=rung.base_qp, codec=codec,
        audio_bitrate=getattr(rung, "audio_bitrate", 0),
    )
