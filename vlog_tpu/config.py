"""Environment-driven configuration.

Reference parity: config.py:95-177 (validated env parsers with VLOG_* names),
config.py:221-260 (quality ladder / segment / timeout envelope),
config.py:317-321 (claim lease + heartbeat). We keep the same env-var names so
an operator of the reference can point their deployment at this framework
unchanged; the parsing/validation machinery is our own.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from pathlib import Path


class ConfigError(ValueError):
    """Raised when an environment override fails validation."""


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name, default)


def _env_int(name: str, default: int, *, lo: int | None = None, hi: int | None = None) -> int:
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        val = int(raw)
    except ValueError as exc:
        raise ConfigError(f"{name}={raw!r} is not an integer") from exc
    if lo is not None and val < lo:
        raise ConfigError(f"{name}={val} below minimum {lo}")
    if hi is not None and val > hi:
        raise ConfigError(f"{name}={val} above maximum {hi}")
    return val


def _env_float(name: str, default: float, *, lo: float | None = None, hi: float | None = None) -> float:
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        val = float(raw)
    except ValueError as exc:
        raise ConfigError(f"{name}={raw!r} is not a number") from exc
    if lo is not None and val < lo:
        raise ConfigError(f"{name}={val} below minimum {lo}")
    if hi is not None and val > hi:
        raise ConfigError(f"{name}={val} above maximum {hi}")
    return val


def _env_bool(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    low = raw.strip().lower()
    if low in ("1", "true", "yes", "on"):
        return True
    if low in ("0", "false", "no", "off"):
        return False
    raise ConfigError(f"{name}={raw!r} is not a boolean")


def _env_path(name: str, default: str) -> Path:
    return Path(os.environ.get(name, default)).expanduser()


# --------------------------------------------------------------------------
# Storage layout
# --------------------------------------------------------------------------

BASE_DIR: Path = _env_path("VLOG_BASE_DIR", "./data")
UPLOAD_DIR: Path = _env_path("VLOG_UPLOAD_DIR", str(BASE_DIR / "uploads"))
VIDEO_DIR: Path = _env_path("VLOG_VIDEO_DIR", str(BASE_DIR / "videos"))
TMP_DIR: Path = _env_path("VLOG_TMP_DIR", str(BASE_DIR / "tmp"))

DATABASE_URL: str = _env_str("VLOG_DATABASE_URL", f"sqlite:///{BASE_DIR / 'vlog.db'}")

MAX_UPLOAD_SIZE_BYTES: int = _env_int(
    "VLOG_MAX_UPLOAD_SIZE_GB", 50, lo=1, hi=1024
) * 1024**3
MIN_FREE_DISK_BYTES: int = _env_int("VLOG_MIN_FREE_DISK_GB", 10, lo=0) * 1024**3

# --------------------------------------------------------------------------
# Quality ladder (reference: README.md:201-212, config.py:221-228)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class QualityRung:
    """One rung of the adaptive-bitrate ladder."""

    name: str            # e.g. "1080p"
    height: int          # frame height; width follows source aspect, mod-16
    video_bitrate: int   # bits/sec target
    audio_bitrate: int   # bits/sec target
    # Base quantization parameter used by the rate controller as a starting
    # point for this rung (tuned so all-intra H.264 lands near the bitrate
    # target for typical content; refined per-segment at encode time).
    base_qp: int = 30


# Full 6-rung ladder matching the reference defaults.
QUALITY_LADDER: tuple[QualityRung, ...] = (
    QualityRung("2160p", 2160, 15_000_000, 192_000, base_qp=30),
    QualityRung("1440p", 1440, 8_000_000, 192_000, base_qp=30),
    QualityRung("1080p", 1080, 5_000_000, 192_000, base_qp=30),
    QualityRung("720p", 720, 2_500_000, 128_000, base_qp=31),
    QualityRung("480p", 480, 1_000_000, 128_000, base_qp=32),
    QualityRung("360p", 360, 600_000, 96_000, base_qp=33),
)

LADDER_BY_NAME: dict[str, QualityRung] = {r.name: r for r in QUALITY_LADDER}


def ladder_for_source(source_height: int) -> tuple[QualityRung, ...]:
    """Rungs at or below the source height (never upscale), always >= 1 rung.

    Reference behavior: qualities above source resolution are skipped
    (transcoder.py quality filtering).
    """
    rungs = tuple(r for r in QUALITY_LADDER if r.height <= max(source_height, 360))
    if not rungs:
        rungs = (QUALITY_LADDER[-1],)
    return rungs


# --------------------------------------------------------------------------
# Segmenting / formats (reference: config.py:234)
# --------------------------------------------------------------------------

SEGMENT_DURATION_S: float = _env_float("VLOG_SEGMENT_DURATION", 6.0, lo=1.0, hi=30.0)
STREAMING_FORMAT: str = _env_str("VLOG_STREAMING_FORMAT", "cmaf")  # "cmaf" | "hls_ts"
DEFAULT_VIDEO_CODEC: str = _env_str("VLOG_VIDEO_CODEC", "h264")

# --------------------------------------------------------------------------
# Job timeout envelope (reference: config.py:247-260)
# --------------------------------------------------------------------------

TRANSCODE_TIMEOUT_MULTIPLIER: float = _env_float("VLOG_TIMEOUT_MULTIPLIER", 2.0, lo=0.1)
TIMEOUT_MIN_S: float = 300.0
TIMEOUT_MAX_S: float = 4 * 3600.0
MAX_VIDEO_DURATION_S: float = 7 * 24 * 3600.0  # 1-week cap (transcoder.py:110)

# Resolution multipliers scale the timeout for heavier rungs
_RESOLUTION_TIMEOUT_MULTIPLIERS: dict[str, float] = {
    "360p": 1.0,
    "480p": 1.2,
    "720p": 1.5,
    "1080p": 2.0,
    "1440p": 2.5,
    "2160p": 3.5,
}


def transcode_timeout_s(duration_s: float, rung_name: str) -> float:
    """Timeout for one rung of one video (duration x global x resolution)."""
    mult = _RESOLUTION_TIMEOUT_MULTIPLIERS.get(rung_name, 2.0)
    raw = duration_s * TRANSCODE_TIMEOUT_MULTIPLIER * mult
    return min(max(raw, TIMEOUT_MIN_S), TIMEOUT_MAX_S)


# --------------------------------------------------------------------------
# Claim / heartbeat protocol (reference: config.py:317-321)
# --------------------------------------------------------------------------

CLAIM_LEASE_S: int = _env_int("VLOG_CLAIM_LEASE_MINUTES", 30, lo=1) * 60
HEARTBEAT_INTERVAL_S: int = _env_int("VLOG_HEARTBEAT_INTERVAL", 30, lo=5)
WORKER_OFFLINE_THRESHOLD_S: int = _env_int("VLOG_WORKER_OFFLINE_THRESHOLD", 300, lo=30)
MAX_JOB_ATTEMPTS: int = _env_int("VLOG_MAX_JOB_ATTEMPTS", 3, lo=1, hi=20)
WORKER_POLL_INTERVAL_S: float = _env_float("VLOG_WORKER_POLL_INTERVAL", 5.0, lo=0.1)

# --------------------------------------------------------------------------
# Coordination plane at fleet scale: long-poll push claims, batched
# claim/heartbeat writes, decoupled lease sweep (jobs/claims.py,
# api/worker_api.py). Wakeups stay ADVISORY: every cap here bounds a
# latency/throughput optimization, never correctness — a shed waiter or
# lost notify degrades to plain poll latency.
# --------------------------------------------------------------------------

# Upper bound the claim endpoint enforces on a request's ``wait_s``
# long-poll park. 0 disables parking entirely (every claim answers
# immediately — the pre-long-poll behavior).
CLAIM_WAIT_MAX_S: float = _env_float("VLOG_CLAIM_WAIT_MAX_S", 30.0, lo=0.0)
# Parked-waiter bound per API process: claim requests beyond this many
# concurrent parks are shed to an immediate 204 (the client falls back
# to its poll interval) instead of pinning more handler tasks/sockets.
CLAIM_MAX_WAITERS: int = _env_int("VLOG_CLAIM_MAX_WAITERS", 256, lo=1)
# Jittered re-check cadence while parked: even with every notify lost
# (dead listener connection, cross-process sqlite) a parked claimant
# re-runs the claim query at roughly this period, so dispatch latency
# degrades to ~this — never to a hung request.
CLAIM_RECHECK_S: float = _env_float("VLOG_CLAIM_RECHECK_S", 2.0, lo=0.1)
# Hard cap on ``max_jobs`` per claim call (jobs per claim transaction).
# Bounds both the transaction's lock footprint and how much work one
# greedy worker can take in a single grab.
CLAIM_BATCH_MAX: int = _env_int("VLOG_CLAIM_BATCH_MAX", 16, lo=1)
# Per-process expired-lease sweeper cadence (jittered ±50% so a fleet of
# processes desynchronizes). The claim path no longer sweeps on every
# claim — it keeps a cheap oldest-expiry probe — so this loop is what
# guarantees lapsed leases are reclaimed and dead-lettered even when
# nobody is claiming. 0 disables the loop (tests that drive sweeps
# explicitly).
SWEEP_INTERVAL_S: float = _env_float("VLOG_SWEEP_INTERVAL_S", 10.0, lo=0.0)
# Write-behind heartbeat coalescing window for the worker API: non-drain
# heartbeats buffer in process and flush as ONE multi-row write per
# window. 0 (default) writes through synchronously. Draining heartbeats
# always write through — a drain transition must be visible immediately.
HEARTBEAT_FLUSH_S: float = _env_float("VLOG_HEARTBEAT_FLUSH_S", 0.0, lo=0.0)

# --------------------------------------------------------------------------
# Multi-tenant QoS + overload protection (jobs/qos.py, jobs/claims.py).
# Per-tenant overrides live in SettingsService dot-keys
# (``qos.tenant.<name>.weight`` / ``.max_queued`` / ``.max_inflight`` /
# ``.deadline_budget_s``); the knobs here are the fleet-wide defaults a
# tenant inherits when no override is written.
# --------------------------------------------------------------------------

# Hard starvation bound for fair-share claiming: any claimable job older
# than this many seconds jumps the weighted fair-share order entirely
# (oldest first), so a low-weight tenant's enqueue->claim latency is
# bounded even under a flood. This is the liveness guarantee the
# tenant-flood bench (bench_coord.py --tenants) regression-gates.
QOS_STARVATION_S: float = _env_float("VLOG_QOS_STARVATION_S", 30.0, lo=0.1)
# Fair-share weight a tenant gets when no per-tenant override is set.
# Relative: a weight-2 tenant is offered ~2x the claims of a weight-1
# tenant while both have backlog. Also the brownout shedding threshold:
# while the enqueue brownout breaker is open, tenants whose weight is
# BELOW this default are shed first (429) at admission.
QOS_DEFAULT_WEIGHT: float = _env_float("VLOG_QOS_DEFAULT_WEIGHT", 1.0,
                                       lo=0.001)
# Default per-tenant queue-depth cap enforced at enqueue (claimable +
# backoff jobs, i.e. queued-not-running). Exceeding it is a 429 +
# Retry-After, never a silent drop. 0 = unlimited.
QOS_MAX_QUEUED: int = _env_int("VLOG_QOS_MAX_QUEUED", 0, lo=0)
# Default per-tenant in-flight (actively claimed) cap enforced by the
# claim query: a tenant at its cap contributes no candidates until a
# claim completes/fails/expires. 0 = unlimited.
QOS_MAX_INFLIGHT: int = _env_int("VLOG_QOS_MAX_INFLIGHT", 0, lo=0)
# Deadline urgency window: a job whose ``deadline_at`` is within this
# many seconds (tenant-overridable) boosts past the fair-share tier,
# ordered by deadline. Starved jobs still rank first.
QOS_DEADLINE_BUDGET_S: float = _env_float("VLOG_QOS_DEADLINE_BUDGET_S",
                                          120.0, lo=0.0)
# Retry-After seconds returned with a queue-depth 429. Brownout sheds
# return the breaker cooldown instead (the queue is not the bottleneck
# there — the database is).
QOS_RETRY_AFTER_S: float = _env_float("VLOG_QOS_RETRY_AFTER_S", 5.0, lo=0.1)
# Tenant-aware queue-depth alert threshold (jobs/alerts.py): any single
# tenant with at least this many claimable jobs queued fires a
# rate-limited webhook naming that tenant. 0 disables the check.
QOS_ALERT_QUEUED: int = _env_int("VLOG_QOS_ALERT_QUEUED", 0, lo=0)
# Cadence of the admin process's periodic tenant queue-depth alert scan.
QOS_ALERT_INTERVAL_S: float = _env_float("VLOG_QOS_ALERT_INTERVAL_S", 60.0,
                                         lo=1.0)
# Autoscale signal (GET /api/fleet/scale-hint): target claimable-job
# backlog per online worker. The hint is the extra workers needed to
# bring backlog/worker down to this target (negative = shrinkable),
# bumped to at least +1 while queue-wait p99 exceeds the starvation
# bound or the enqueue brownout breaker is open.
QOS_SCALE_TARGET: int = _env_int("VLOG_QOS_SCALE_TARGET", 8, lo=1)
# Sliding window over server-side ``queue.wait`` spans used for the
# scale hint's p99 (seconds of history considered).
QOS_WAIT_WINDOW_S: float = _env_float("VLOG_QOS_WAIT_WINDOW_S", 300.0,
                                      lo=10.0)

# --------------------------------------------------------------------------
# SLO plane (obs/slo.py): declarative objectives per plane evaluated as
# multi-window burn rates over the runtime registry + job_spans, served
# at GET /api/slo and exported as vlog_slo_* families.
# --------------------------------------------------------------------------

# Fast burn-rate window: catches an acute burn (page-grade signal when
# both windows fire — the classic multi-window multi-burn rule).
SLO_FAST_WINDOW_S: float = _env_float("VLOG_SLO_FAST_WINDOW_S", 300.0,
                                      lo=10.0)
# Slow burn-rate window: confirms the fast window isn't a blip.
SLO_SLOW_WINDOW_S: float = _env_float("VLOG_SLO_SLOW_WINDOW_S", 3600.0,
                                      lo=60.0)
# Cadence of the admin process's background SLO evaluation loop (which
# also fires burn alerts through the webhook sink). 0 disables the
# loop; GET /api/slo still evaluates on demand.
SLO_EVAL_S: float = _env_float("VLOG_SLO_EVAL_S", 30.0, lo=0.0)
# Bounded ring of slow-outlier exemplars (trace_id + attrs) kept by the
# SLO plane; each links to GET /api/jobs/{id}/trace.
SLO_EXEMPLARS: int = _env_int("VLOG_SLO_EXEMPLARS", 16, lo=1, hi=256)
# Burn-rate threshold: an objective alerts while BOTH windows burn at
# or above this multiple of its error budget (1.0 = budget-rate).
SLO_BURN_ALERT: float = _env_float("VLOG_SLO_BURN_ALERT", 1.0, lo=0.1)

# On-demand device profiler (obs/profiler.py): artifact root for
# jax.profiler.trace sessions started over the worker command channel.
# Empty = BASE_DIR/profiles. Sessions are confined to this directory.
PROFILE_DIR: str = _env_str("VLOG_PROFILE_DIR", "")
# Hard cap on one profiling session's duration; requests clamp to it so
# a fat-fingered duration can't leave tracing on for an hour.
PROFILE_MAX_S: float = _env_float("VLOG_PROFILE_MAX_S", 60.0, lo=1.0)

# Short TTL for the DB-derived gauge block of /metrics (job-state
# GROUP BY, workers-online count, per-tenant queue GROUP BY): scrapes
# inside the TTL reuse the cached block so a tight Prometheus interval
# cannot become DB load. 0 = recompute every scrape.
METRICS_DB_TTL_S: float = _env_float("VLOG_METRICS_DB_TTL_S", 5.0, lo=0.0)

# Default fractional tolerance for the bench-trend regression gate
# (obs/benchtrend.py): the latest record of a series may fall this far
# below the best prior (or rise this far above it for lower-is-better
# metrics) before it flags. Per-metric overrides live in the module.
BENCHTREND_TOL: float = _env_float("VLOG_BENCHTREND_TOL", 0.5, lo=0.01)

# --------------------------------------------------------------------------
# Preemption-tolerant drain (worker/drain.py): on SIGTERM or a
# preemption notice the worker stops claiming, lets in-flight compute
# finish and flush (leases heartbeat-extended), then force-cancels and
# requeues anything still running once the grace window lapses.
# --------------------------------------------------------------------------

# Seconds between the first termination/preemption notice and the
# force-cancel of still-running jobs. 0 = cancel immediately (the
# pre-drain SIGTERM behavior). Size it just under the platform's
# eviction window (k8s terminationGracePeriodSeconds, the TPU/GCE
# preemption notice lead).
DRAIN_GRACE_S: float = _env_float("VLOG_DRAIN_GRACE_S", 120.0, lo=0.0)
# Preemption notice channels; empty = not watched. The file form is a
# path a node agent touches on eviction notice; the URL form is a
# metadata endpoint that answers 200 once eviction is scheduled.
PREEMPTION_FILE: str = _env_str("VLOG_PREEMPTION_FILE", "")
PREEMPTION_URL: str = _env_str("VLOG_PREEMPTION_URL", "")
# Notice poll cadence (both channels).
PREEMPTION_POLL_S: float = _env_float("VLOG_PREEMPTION_POLL_S", 2.0, lo=0.1)

# --------------------------------------------------------------------------
# Failure plane: retry backoff, circuit breaker, stall watchdog
# --------------------------------------------------------------------------

# Jittered exponential backoff between retry attempts: attempt N becomes
# claimable no earlier than base * 2^(N-1), capped, with +/-50% jitter
# (jobs/claims.py retry_backoff_s). Base 0 disables backoff entirely.
RETRY_BACKOFF_BASE_S: float = _env_float("VLOG_RETRY_BACKOFF_BASE", 30.0, lo=0.0)
RETRY_BACKOFF_CAP_S: float = _env_float("VLOG_RETRY_BACKOFF_CAP", 1800.0, lo=0.0)
# Worker-side circuit breaker (worker/breaker.py): this many CONSECUTIVE
# compute failures stops the daemon claiming; after the cooldown one
# half-open probe job decides whether to close or re-open.
BREAKER_FAILURE_THRESHOLD: int = _env_int("VLOG_BREAKER_THRESHOLD", 5, lo=1)
BREAKER_COOLDOWN_S: float = _env_float("VLOG_BREAKER_COOLDOWN", 60.0, lo=0.0)
# Stall watchdog: cancel in-flight compute whose progress has not advanced
# within this window, even while lease renewals keep it nominally alive.
# 0 disables the watchdog.
STALL_WINDOW_S: float = _env_float("VLOG_STALL_WINDOW", 900.0, lo=0.0)
# Device-fault quarantine (parallel/scheduler.py): a slot's devices are
# quarantined after this many device-classified faults (parallel/faults.py)
# are attributed to them; a quarantined device rejoins the rotation only
# after the cheap probe computation passes on it.
QUARANTINE_THRESHOLD: int = _env_int("VLOG_QUARANTINE_THRESHOLD", 1, lo=1)
# Cadence of the quarantined-device probe sweep in the worker daemon;
# 0 disables the loop (devices then stay quarantined until restart or an
# explicit probe_quarantined call).
DEVICE_PROBE_INTERVAL_S: float = _env_float(
    "VLOG_DEVICE_PROBE_INTERVAL_S", 60.0, lo=0.0)
# Coordination-plane brownout breaker (worker/brownout.py): this many
# CONSECUTIVE transient DB/API errors in a worker's claim loop mark the
# worker browned-out (readiness degrades, claim attempts pause on
# jittered backoff) until the plane answers again.
DB_BREAKER_THRESHOLD: int = _env_int("VLOG_DB_BREAKER_THRESHOLD", 3, lo=1)
DB_BREAKER_COOLDOWN_S: float = _env_float(
    "VLOG_DB_BREAKER_COOLDOWN", 15.0, lo=0.0)

# --------------------------------------------------------------------------
# Storage integrity plane: orphan GC (storage/gc.py). MIN_FREE_DISK_BYTES
# above is the admission floor enforced by storage/integrity.py:
# uploads answer 507 and workers pause claiming when free space on the
# target volume drops below it (0 disables admission control).
# --------------------------------------------------------------------------

# Periodic sweep cadence in the admin API process; 0 disables the loop
# (the admin trigger endpoint still works).
GC_INTERVAL_S: float = _env_float("VLOG_GC_INTERVAL", 3600.0, lo=0.0)
# A temp (.part/.tmp/.upload-*) younger than this may be an in-flight
# transfer — only older ones are reclaimed.
GC_TEMP_MAX_AGE_S: float = _env_float("VLOG_GC_TEMP_MAX_AGE", 6 * 3600.0,
                                      lo=0.0)
# Soft-deleted videos are restorable; their output trees survive this
# long after deleted_at before the sweeper reclaims them.
GC_DELETED_RETENTION_S: float = _env_float("VLOG_GC_DELETED_RETENTION",
                                           7 * 86400.0, lo=0.0)

# --------------------------------------------------------------------------
# Observability plane (obs/): job traces + the process-wide metrics
# registry. Tracing writes one root span per job life plus claim/
# complete markers and worker attempt spans to the job_spans table.
# --------------------------------------------------------------------------

# Gate for span creation/persistence (metrics are always on — a counter
# bump is too cheap to gate). Off = no job_spans writes anywhere.
TRACE_ENABLED: bool = _env_bool("VLOG_TRACE_ENABLED", True)

# --------------------------------------------------------------------------
# Delivery plane (delivery/): origin-side segment cache + admission
# between serve_media and the filesystem/DB. Steady-state playback must
# not touch Postgres or re-open published segments per request.
# --------------------------------------------------------------------------

# Byte budget of the in-memory LRU segment cache (0 disables caching;
# requests still flow through the same response builder, so cached and
# uncached responses stay byte-identical).
DELIVERY_CACHE_BYTES: int = _env_int(
    "VLOG_DELIVERY_CACHE_BYTES", 256 * 1024**2, lo=0)
# Distinct cache-miss disk reads allowed in flight at once; misses past
# the bound answer 503 + Retry-After instead of queueing on the volume
# (single-flight already collapses same-segment misses to one read).
DELIVERY_MAX_INFLIGHT_READS: int = _env_int(
    "VLOG_DELIVERY_MAX_INFLIGHT_READS", 64, lo=1)
# Mutable manifests (.m3u8/.mpd) cache for this long; segments are
# immutable (digest-keyed) and live until evicted or invalidated.
DELIVERY_MANIFEST_TTL_S: float = _env_float(
    "VLOG_DELIVERY_MANIFEST_TTL", 2.0, lo=0.0)
# Segment bodies are pinned by default (0): in-process invalidation
# covers every publish/re-encode path and steady state stays
# zero-syscall. In a SPLIT deployment — trees mutated by an admin or
# worker PROCESS the serving process can't see — invalidation cannot
# fan out, so set a TTL here to bound how long a republished segment
# may serve stale from this cache.
DELIVERY_SEGMENT_TTL_S: float = _env_float(
    "VLOG_DELIVERY_SEGMENT_TTL", 0.0, lo=0.0)
# Publish-state (slug -> ready/deleted/missing) cache TTL: the window in
# which a publish/delete in ANOTHER process may be stale here. In-process
# mutations invalidate explicitly and are visible immediately.
DELIVERY_STATE_TTL_S: float = _env_float(
    "VLOG_DELIVERY_STATE_TTL", 5.0, lo=0.0)
# Objects larger than this bypass the buffer cache and stream from disk
# (sized well above any 4-6 s segment; catches source downloads).
DELIVERY_MAX_ENTRY_BYTES: int = _env_int(
    "VLOG_DELIVERY_MAX_ENTRY_BYTES", 32 * 1024**2, lo=1)

# ---- distributed tier (L2 + peer-fill + prewarm + sendfile) --------------

# Byte budget of the disk-backed L2 below the RAM LRU (0 disables the
# disk tier entirely). Entries spill here on L1 eviction and on fill;
# every read back is sha256-verified against the publish manifest before
# it can serve, so a corrupt or truncated spill refills instead of
# serving.
DELIVERY_L2_BYTES: int = _env_int("VLOG_DELIVERY_L2_BYTES", 0, lo=0)
# Directory holding the digest-named L2 store (content-addressed:
# <sha256[:2]>/<sha256>). Safe to wipe at any time — it is purely a
# warm-set cache rebuilt from the origin tree.
DELIVERY_L2_DIR: Path = _env_path(
    "VLOG_DELIVERY_L2_DIR", str(BASE_DIR / "delivery-l2"))
# Comma-separated base URLs of every origin process in the delivery
# ring (including this one). Empty = no ring: every miss fills from
# local disk. With a ring, a miss on a non-owner origin fetches the
# object from its rendezvous-hash owner over the public /videos route
# (digest-checked) before falling back to local disk.
DELIVERY_PEERS: tuple[str, ...] = tuple(
    u.strip().rstrip("/") for u in
    _env_str("VLOG_DELIVERY_PEERS", "").split(",") if u.strip())
# This process's own base URL as it appears in VLOG_DELIVERY_PEERS, so
# the ring can tell "I am the owner" from "fetch from the owner". Empty
# with a non-empty ring means this process owns nothing (pure edge).
DELIVERY_SELF_URL: str = _env_str(
    "VLOG_DELIVERY_SELF_URL", "").rstrip("/")
# Per-object peer-fetch budget; a slow or down owner past this falls
# back to local fill and starts a short cooldown for that peer.
DELIVERY_PEER_TIMEOUT_S: float = _env_float(
    "VLOG_DELIVERY_PEER_TIMEOUT", 2.0, lo=0.1)
# How many leading media segments of each rung finalize_ready warms
# into the cache (plus every init segment). 0 disables prewarm.
DELIVERY_PREWARM_SEGMENTS: int = _env_int(
    "VLOG_DELIVERY_PREWARM_SEGMENTS", 2, lo=0)
# L2 hits at or above this size serve zero-copy (os.sendfile via a
# file response) instead of buffering into the RAM LRU; smaller hits
# promote to L1 as usual.
DELIVERY_SENDFILE_BYTES: int = _env_int(
    "VLOG_DELIVERY_SENDFILE_BYTES", 8 * 1024**2, lo=1)
# How long a peer that failed a fill (transport error or non-503
# status) sits out before fills route to it again. A 503 shed with a
# Retry-After header overrides this with the peer's own number.
DELIVERY_PEER_COOLDOWN_S: float = _env_float(
    "VLOG_DELIVERY_PEER_COOLDOWN_S", 5.0, lo=0.0)

# ---- self-healing fabric (gossip membership + hedged fills + heat) -------

# Mean seconds between gossip heartbeat rounds (each round probes every
# known peer over GET /api/delivery/gossip). 0 disables the probe loop:
# membership then moves only on fill failures/successes.
DELIVERY_GOSSIP_INTERVAL_S: float = _env_float(
    "VLOG_DELIVERY_GOSSIP_INTERVAL", 1.0, lo=0.0)
# Probe-interval jitter as a fraction of the interval (bounded to
# [interval*(1-j), interval*(1+j)]) so N origins never probe in
# lockstep and suspect windows desynchronize across the fleet.
DELIVERY_GOSSIP_JITTER: float = _env_float(
    "VLOG_DELIVERY_GOSSIP_JITTER", 0.25, lo=0.0, hi=0.9)
# Consecutive transport/timeout failures (probe or fill) before an
# alive peer turns suspect. Suspects keep their ring ownership but
# fills route around them immediately.
DELIVERY_GOSSIP_SUSPECT_AFTER: int = _env_int(
    "VLOG_DELIVERY_GOSSIP_SUSPECT_AFTER", 2, lo=1)
# A suspect silent this long goes down: it leaves the ownership set and
# the ring version bumps, so rendezvous routing rebalances its keys.
# One successful heartbeat rejoins it.
DELIVERY_GOSSIP_DOWN_S: float = _env_float(
    "VLOG_DELIVERY_GOSSIP_DOWN", 3.0, lo=0.0)
# How long a digest-liar peer (served bytes failing the manifest sha256
# check) is quarantined out of the ownership set, regardless of
# reachability.
DELIVERY_GOSSIP_QUARANTINE_S: float = _env_float(
    "VLOG_DELIVERY_GOSSIP_QUARANTINE", 60.0, lo=0.0)
# Latency budget before a miss routed to the owner launches a hedge
# fill to the next-ranked peer (first digest-valid response wins, the
# loser is cancelled). Once enough fill samples accumulate the budget
# adapts to the observed p95 fill latency, clamped to [this/4, 4*this].
# 0 disables hedging.
DELIVERY_HEDGE_MS: float = _env_float(
    "VLOG_DELIVERY_HEDGE_MS", 250.0, lo=0.0)
# Half-life (seconds) of the per-slug exponential heat decay behind
# popularity-aware L2 admission. Heat rises by 1 per request to the
# slug and halves every this-many seconds.
DELIVERY_HEAT_HALFLIFE_S: float = _env_float(
    "VLOG_DELIVERY_HEAT_HALFLIFE", 300.0, lo=1.0)
# Minimum slug heat for a body to be admitted into the disk L2
# (one-hit-wonders bypass the spill). 0 admits everything — the
# pre-fabric behavior.
DELIVERY_L2_ADMIT_HEAT: float = _env_float(
    "VLOG_DELIVERY_L2_ADMIT_HEAT", 0.0, lo=0.0)
# Slugs at or above this heat resist L2 eviction: the sweep gives their
# entries a second chance (bounded) and evicts colder bytes first.
# 0 keeps pure LRU eviction.
DELIVERY_L2_HOT_HEAT: float = _env_float(
    "VLOG_DELIVERY_L2_HOT_HEAT", 0.0, lo=0.0)

# --------------------------------------------------------------------------
# Transcription (reference: config.py:263-267)
# --------------------------------------------------------------------------

WHISPER_MODEL: str = _env_str("VLOG_WHISPER_MODEL", "small")
# Local HF-format weights directory (no egress: the operator provisions it).
WHISPER_DIR: str = _env_str("VLOG_WHISPER_DIR", "")
WHISPER_CHUNK_S: float = 30.0       # model window
WHISPER_OVERLAP_S: float = 5.0      # chunk overlap for stitching
# Beam width for decoding. The reference runs faster-whisper beam_size=5
# (worker/transcription.py:92-133); 1 = the cheaper greedy scan.
WHISPER_BEAM: int = _env_int("VLOG_WHISPER_BEAM", 5, lo=1, hi=16)
TRANSCRIPTION_ENABLED: bool = _env_bool("VLOG_TRANSCRIPTION_ENABLED", True)
# Transcript model directory (config.json + model.safetensors, lm/load.py).
# Empty = off: no digest job is enqueued after a transcription.
DIGEST_DIR: str = _env_str("VLOG_DIGEST_DIR", "")

# Continuous-batching ASR engine (asr/engine.py): one shared Whisper
# serving every transcription job on the worker.
# Widest batch the engine packs per tick; batches run at power-of-two
# bucket shapes up to this, so decode stays recompile-free.
ASR_BATCH_WINDOWS: int = _env_int("VLOG_ASR_BATCH_WINDOWS", 8, lo=1, hi=64)
# Coalescing delay per tick: how long the engine lets windows from
# concurrent jobs accumulate before packing a batch. 0 disables.
ASR_TICK_S: float = _env_float("VLOG_ASR_TICK_S", 0.05, lo=0.0, hi=5.0)
# Window-queue bound; submits block (backpressure) once this many
# windows are queued across all jobs.
ASR_QUEUE_MAX: int = _env_int("VLOG_ASR_QUEUE_MAX", 256, lo=8, hi=8192)
# Whisper weight storage/compute precision (asr/load.py quantizes at
# load): "f32" (exact, the byte-identity reference), "bf16" (half-size
# weight storage, dequant-on-use matmuls), "int8" (per-output-channel
# symmetric weight quantization, dequant-on-use). Quantized runs trade
# the solo-vs-packed byte-identity-vs-f32 gate for WER parity; packing
# invariance (solo vs co-batched) holds in every mode.
WHISPER_QUANT: str = _env_str("VLOG_WHISPER_QUANT", "f32")

# --------------------------------------------------------------------------
# Sprites (reference: config.py:572-593)
# --------------------------------------------------------------------------

SPRITE_INTERVAL_S: float = _env_float("VLOG_SPRITE_INTERVAL", 10.0, lo=1.0)
SPRITE_TILE_W: int = _env_int("VLOG_SPRITE_WIDTH", 160, lo=16)
SPRITE_TILE_H: int = _env_int("VLOG_SPRITE_HEIGHT", 90, lo=16)
SPRITE_GRID: int = 10  # 10x10 tiles per sheet
SPRITE_MAX_SHEETS: int = _env_int("VLOG_SPRITE_MAX_SHEETS", 20, lo=1)

# --------------------------------------------------------------------------
# API services
# --------------------------------------------------------------------------

PUBLIC_PORT: int = _env_int("VLOG_PUBLIC_PORT", 9000, lo=1, hi=65535)
ADMIN_PORT: int = _env_int("VLOG_ADMIN_PORT", 9001, lo=1, hi=65535)
WORKER_API_PORT: int = _env_int("VLOG_WORKER_API_PORT", 9002, lo=1, hi=65535)
WORKER_API_URL: str = _env_str("VLOG_WORKER_API_URL", f"http://127.0.0.1:{WORKER_API_PORT}")
ADMIN_SECRET: str = _env_str("VLOG_ADMIN_SECRET", "")
# Set behind TLS: marks the admin session cookie Secure so the 12h
# bearer token never rides a cleartext hop. Off by default only because
# Secure cookies are silently dropped by browsers on plain-HTTP dev
# deployments.
ADMIN_COOKIE_SECURE: bool = _env_bool("VLOG_ADMIN_COOKIE_SECURE", False)
DOWNLOADS_ENABLED: bool = _env_bool("VLOG_DOWNLOADS_ENABLED", False)
# SSRF guard: webhook targets on private/loopback networks are refused
# unless explicitly allowed (reference webhook_service.py:143).
WEBHOOK_ALLOW_PRIVATE: bool = _env_bool("VLOG_WEBHOOK_ALLOW_PRIVATE", False)

# --------------------------------------------------------------------------
# TPU backend
# --------------------------------------------------------------------------

TPU_ENABLED: bool = _env_bool("VLOG_TPU_ENABLED", True)
# GOP structure: "p" = I + P chains (inter prediction; the bitrate-
# efficient default), "intra" = every frame an IDR (the round-1/2 mode).
GOP_MODE: str = _env_str("VLOG_GOP_MODE", "p")
# Target chain length (frames per I+P group). The backend picks the
# largest divisor of frames-per-segment not exceeding this, so every
# CMAF segment still starts on an IDR.
GOP_LEN: int = _env_int("VLOG_GOP_LEN", 24, lo=1, hi=256)
# Integer motion search radius (pels).
MOTION_SEARCH_RADIUS: int = _env_int("VLOG_MOTION_SEARCH", 8, lo=1, hi=32)
# H.264 entropy coder: "cabac" (default — 10-45% smaller streams, the
# profile x264 ships by default) or "cavlc" (~2.5x faster host entropy
# when the host stage, not the device, is the bottleneck). Both have
# native C coders. Changing this mid-tree invalidates partial resume
# state (segments must share one PPS); re-transcode with force.
H264_ENTROPY: str = _env_str("VLOG_H264_ENTROPY", "cabac")
# In-loop deblocking (spec 8.7) for the chain path: smooths block edges
# inside the prediction loop (the reference gets this from x264, which
# always deblocks). Costs a wavefront pass per reconstructed frame on
# device; intra-only mode leaves it off (deblocking is display-only
# there and the device pass is the headline bench).
H264_DEBLOCK: bool = _env_bool("VLOG_H264_DEBLOCK", True)
# AV1 delegated-encoder speed (libaom cpu-used 0-8 / SVT preset): the
# reference's AV1 is hardware-delegated (hwaccel.py:555-646); ours rides
# the system encoder libraries (backends/av1_path.py).
AV1_SPEED: int = _env_int("VLOG_AV1_SPEED", 8, lo=0, hi=8)
# HEVC 2NxN/Nx2N inter partitions (oracle-proven; big wins on
# split-motion content, but the mode-decision penalty is uncalibrated
# for mixed content and partitioned slices entropy-code in Python —
# opt-in until both are resolved).
HEVC_PARTITIONS: bool = _env_bool("VLOG_HEVC_PARTITIONS", False)
# Spec-8.7.2 in-loop deblocking in the HEVC DSP (codecs/hevc/deblock.py)
HEVC_DEBLOCK: bool = _env_bool("VLOG_HEVC_DEBLOCK", True)
# Frames per device-batch staged to HBM per encode dispatch. GOP size for the
# all-intra encoder is a packaging concept (segment boundary), so this is a
# pure throughput/memory knob.
TPU_FRAME_BATCH: int = _env_int("VLOG_TPU_FRAME_BATCH", 8, lo=1, hi=256)
# Batches allowed in flight on the consume side of the transcode
# pipeline (parallel/executor.py): at depth D, dispatch of batch N,
# the device->host pull of batch N-1, and entropy/packaging of batch
# N-2 proceed concurrently (D-1 batches consume while one stages).
# Depth 1 is the fully-serial loop; the rate controllers' calibration
# "hunting" phase always drains to depth 0 regardless.
PIPELINE_DEPTH: int = _env_int("VLOG_PIPELINE_DEPTH", 2, lo=1, hi=16)
# Host entropy worker threads shared by every rung's frame fan-out (one
# pool per run, parallel/executor.py). Default derives from the host
# core count: the C entropy coders release the GIL, so throughput
# scales ~linearly until cores run out.
ENTROPY_THREADS: int = _env_int(
    "VLOG_ENTROPY_THREADS", max(2, min(32, os.cpu_count() or 8)),
    lo=1, hi=256)
# Mesh axis layout for the ladder's 2-D (data × rung) grid, parsed by
# parallel.mesh.resolve_mesh_shape: "data:2,rung:4" splits 8 devices
# into 4 rung columns of 2-wide data submeshes; "auto" picks the shape
# from batch size and rung count; legacy 1-D specs ("data:-1", "data:8")
# keep the pure data-parallel layout (rung defaults to 1). One axis may
# be -1 (fill from the device count); the rung axis clamps to the
# ladder's rung count. Non-ladder programs (make_mesh callers) read the
# same spec and ignore axes they don't use.
TPU_MESH_SPEC: str = _env_str("VLOG_TPU_MESH", "data:-1")
# Fused Pallas ladder kernel (ops/pallas_ladder.py): resize + quantize +
# uint8 cast in one VMEM pass per rung instead of three XLA dispatches.
# "auto" and "0" are the XLA path on every platform; "1" asks for the
# kernel — interpreted on CPU (the byte-identity test vehicle), and on a
# TPU a Mosaic refusal raises (as of PR 21 Mosaic refuses the kernel as
# written; see the module docstring and ROADMAP Speed item 5).
PALLAS: str = _env_str("VLOG_PALLAS", "auto")
# Mesh job slots (parallel/scheduler.py): the process's devices partition
# into this many equal-width slots so the scheduler can admit that many
# queued jobs onto the mesh CONCURRENTLY (e.g. 2 on a v5e-8 = two
# 4-chip jobs instead of back-to-back full-mesh runs). 1 = the classic
# one-job-owns-every-chip mode. Work-conserving: a lone job always
# leases the full mesh regardless of this knob; widths renegotiate at
# job boundaries.
MESH_SLOTS: int = _env_int("VLOG_MESH_SLOTS", 1, lo=1, hi=64)

CODE_VERSION: str = "1"


def ensure_dirs() -> None:
    """Create the storage tree (idempotent)."""
    for p in (BASE_DIR, UPLOAD_DIR, VIDEO_DIR, TMP_DIR):
        p.mkdir(parents=True, exist_ok=True)
