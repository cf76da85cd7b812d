"""Multi-chip dry run body: the FULL sharded ladder step on n devices,
plus the mesh-shape / scheduler throughput harness.

Run as ``python -m vlog_tpu.parallel.dryrun N`` in a subprocess whose
environment pins ``JAX_PLATFORMS=cpu`` and
``--xla_force_host_platform_device_count=N`` — the platform decision must
happen before any backend is touched.

The body is the real multi-chip path the TPU worker dispatches per frame
batch: ``shard_map`` over a data mesh, per-device resize + full intra
H.264 DSP for every rung, cross-device ``psum`` PSNR reduction over ICI
(SURVEY.md §2d.5).

After the correctness asserts, the harness measures and prints (as the
final JSON line the MULTICHIP_r*.json record captures; the same numbers
are appended as labeled records to ``MULTICHIP.json`` in the
BENCH_delivery/BENCH_coord format so shape_fps trajectories compare
across rounds instead of each round overwriting the last):

- per-mesh-shape chain-ladder throughput over the 2-D (data × rung)
  grid — data-only shapes (1x1/2x1/4x1/8x1) plus the full-device 2-D
  shapes (4x2/2x4) — on two workloads: "full" (one chain per data
  slot) and "small_batch" (2 chains regardless of shape, the workload
  where data-only padding wastes most of the mesh) (``shape_fps``), and
- the mesh job scheduler's 2-slots-vs-1 comparison: two queued jobs
  whose batches underfill the full mesh, run serialized on full-mesh
  leases vs concurrently on 2 narrow slots through the REAL
  ``parallel.scheduler`` admit/acquire path (``sched``: wall seconds,
  jobs/min, speedup) — the number the ISSUE-6 acceptance criterion
  reads.
"""

from __future__ import annotations

import json
import sys
import time


def run(n_devices: int) -> None:
    import jax
    import numpy as np

    from vlog_tpu.parallel import make_mesh, sharded_ladder_step, shard_frames
    from vlog_tpu.parallel.ladder import valid_mask
    from vlog_tpu.parallel.mesh import pad_batch

    devices = jax.devices()[:n_devices]
    assert len(devices) == n_devices, (
        f"need {n_devices} cpu devices, have {len(jax.devices())} "
        "(xla_force_host_platform_device_count not honored?)")
    mesh = make_mesh("data:-1", devices=devices)

    # Full sharded step on tiny shapes: per-device resize+encode of its
    # frame shard for every rung + psum PSNR over the mesh.
    rungs = (("64p", 64, 96, 28), ("32p", 32, 48, 30))
    n, h, w = n_devices, 96, 128          # one frame per device
    step, mats = sharded_ladder_step(mesh, rungs, h, w)

    rng = np.random.default_rng(1)
    y = rng.integers(0, 256, (n, h, w)).astype(np.uint8)
    u = rng.integers(0, 256, (n, h // 2, w // 2)).astype(np.uint8)
    v = rng.integers(0, 256, (n, h // 2, w // 2)).astype(np.uint8)
    (y, u, v), real = pad_batch(n_devices, y, u, v)
    ys, us, vs = shard_frames(mesh, y, u, v)
    (valid,) = shard_frames(mesh, np.asarray(valid_mask(y.shape[0], real)))

    out, stats = step(ys, us, vs, mats, valid)
    jax.block_until_ready(out)
    for name, _, _, _ in rungs:
        psnr = float(stats[name])
        assert 10.0 < psnr < 99.0, f"rung {name}: implausible PSNR {psnr}"
        assert out[name]["luma_ac"].shape[0] == n_devices

    # The I+P chain production path (GOP_MODE="p"): one chain per device,
    # sharded on the chain axis (inter prediction chains WITHIN a device,
    # never across — the temporal-dependence adaptation of §2d.5).
    from vlog_tpu.parallel.ladder import ladder_chain_program, ladder_matrices  # noqa: F401

    clen = 3
    from vlog_tpu import config

    # Match production: the in-loop wavefront filter must compile and
    # shard with the chain exactly as the backend will dispatch it.
    cfn, cmats = ladder_chain_program(rungs, h, w, search=4, mesh=mesh,
                                      deblock=config.H264_DEBLOCK)
    cy = rng.integers(0, 256, (n_devices, clen, h, w)).astype(np.uint8)
    cu = rng.integers(0, 256, (n_devices, clen, h // 2, w // 2)).astype(np.uint8)
    cv = rng.integers(0, 256, (n_devices, clen, h // 2, w // 2)).astype(np.uint8)
    qps = {name: np.full((n_devices, clen), qp, np.int32)
           for name, _, _, qp in rungs}
    # exercise the device-side in-chain rate adaptation exactly as the
    # production backend dispatches it (alpha > 0 -> adjustment live)
    rc = {name: {"budget": np.float32(2000.0),
                 "alpha": np.float32(0.5)}
          for name, _, _, _ in rungs}
    cy, cu, cv = shard_frames(mesh, cy, cu, cv)
    qps = {k: shard_frames(mesh, q)[0] for k, q in qps.items()}
    couts = cfn(cy, cu, cv, cmats, qps, rc)
    jax.block_until_ready(couts)
    for name, _, _, _ in rungs:
        ro = couts[name]
        assert ro["p_luma"].shape[:2] == (n_devices, clen - 1)
        assert ro["mv"].shape[:2] == (n_devices, clen - 1)
        assert ro["sse_y"].shape == (n_devices, clen)

    # The fused HEVC chain ladder (codec="h265" re-encodes), sharded the
    # same way on the chain axis.
    from vlog_tpu.parallel.hevc_ladder import hevc_chain_ladder_program

    hfn, hmats = hevc_chain_ladder_program(rungs, h, w, search=4, mesh=mesh)
    houts = hfn(cy, cu, cv, hmats, qps, rc)
    jax.block_until_ready(houts)
    for name, _, _, _ in rungs:
        ro = houts[name]
        assert ro["p_luma"].shape[:2] == (n_devices, clen - 1)
        assert ro["sse_y"].shape == (n_devices, clen)
        assert ro["qp_eff"].shape == (n_devices, clen)

    print(f"dryrun ok: {n_devices} devices, rungs "
          f"{[(r[0], round(float(stats[r[0]]), 2)) for r in rungs]}, "
          f"chain clen={clen} ok, hevc chain ok")

    # The shape sweep wants enough rungs for a real rung axis (r up to
    # 4 columns); all sweep rungs fit the 96x128 source.
    sweep_rungs = (("96p", 96, 128, 26), ("64p", 64, 96, 28),
                   ("48p", 48, 64, 29), ("32p", 32, 48, 30))
    shape_fps = measure_mesh_shapes(devices, sweep_rungs, h, w, clen)
    sched = measure_scheduler_packing(devices, rungs, h, w, clen)
    record = {"multichip": "ok", "devices": n_devices,
              "shape_fps": shape_fps, "sched": sched}
    try:
        _append_records("MULTICHIP.json",
                        _multichip_records(n_devices, shape_fps, sched))
    except OSError:
        pass   # record trail is best-effort; the JSON line below is not
    print(json.dumps(record), flush=True)


def _append_records(path: str, records: list[dict]) -> None:
    """Labeled-record trail (the BENCH_delivery/BENCH_coord idiom):
    read the existing list, extend, rewrite — rounds accumulate."""
    import os

    existing: list = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                loaded = json.load(f)
            if isinstance(loaded, list):
                existing = loaded
        except (OSError, ValueError):
            existing = []
    existing.extend(records)
    with open(path, "w") as f:
        json.dump(existing, f, indent=1)
        f.write("\n")


def _multichip_records(n_devices: int, shape_fps: dict,
                       sched: dict) -> list[dict]:
    from vlog_tpu import config
    from vlog_tpu.ops.pallas_ladder import use_pallas
    from vlog_tpu.parallel.compile_cache import compile_seconds

    ts = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    # raw-speed plane stamps on every record: kernel plane, whisper
    # quant mode, this process's metered XLA compile seconds
    speed = {"pallas": use_pallas(),
             "whisper_quant": config.WHISPER_QUANT,
             "compile_s": round(compile_seconds(), 3)}
    recs = []
    for workload in ("full", "small_batch"):
        for label, fps in (shape_fps.get(workload) or {}).items():
            recs.append({
                "step": f"{workload}:{label}",
                "metric": "ladder_chain_fps",
                "fps": fps,
                "timestamp": ts,
                "config": {"devices": n_devices, "mesh_shape": label,
                           "workload": workload, **speed}})
    summary = shape_fps.get("small_batch_summary")
    if summary:
        recs.append({"step": "small_batch_summary",
                     "metric": "ladder_shape_win_x",
                     "win_x": summary.get("win_x"),
                     "timestamp": ts,
                     "config": {"devices": n_devices, **summary, **speed}})
    if sched and "speedup" in sched:
        recs.append({"step": "sched_packing",
                     "metric": "sched_speedup_x",
                     "speedup_x": sched["speedup"],
                     "timestamp": ts,
                     "config": {"devices": n_devices,
                                "jobs": sched.get("jobs"),
                                "slot_widths": sched.get("slot_widths"),
                                **speed}})
    return recs


def _chain_batch(rng_seed: int, n_chains: int, clen: int, h: int, w: int):
    import numpy as np

    rng = np.random.default_rng(rng_seed)
    y = rng.integers(0, 256, (n_chains, clen, h, w)).astype(np.uint8)
    u = rng.integers(0, 256,
                     (n_chains, clen, h // 2, w // 2)).astype(np.uint8)
    v = rng.integers(0, 256,
                     (n_chains, clen, h // 2, w // 2)).astype(np.uint8)
    return y, u, v


def _dispatch_chains(fn, mats, mesh, rungs, y, u, v, clen):
    """One chain-ladder dispatch (sharded when mesh is not None);
    blocks until the device work completes and pulls one output —
    the dispatch+pull shape the production consume loop pays."""
    import jax
    import numpy as np

    from vlog_tpu.parallel.mesh import shard_frames

    n_chains = y.shape[0]
    qps = {name: np.full((n_chains, clen), qp, np.int32)
           for name, _, _, qp in rungs}
    rc = {name: {"budget": np.float32(2000.0), "alpha": np.float32(0.0)}
          for name, _, _, _ in rungs}
    if mesh is not None:
        y, u, v = shard_frames(mesh, y, u, v)
        qps = {k: shard_frames(mesh, q)[0] for k, q in qps.items()}
    outs = fn(y, u, v, mats, qps, rc)
    jax.block_until_ready(outs)
    np.asarray(outs[rungs[0][0]]["sse_y"])


def _dispatch_grid(prog, rungs, y, u, v, clen):
    """One 2-D grid chain-ladder dispatch: pad the chain axis to the
    grid's DATA width (not the device count — the 2-D win), stage per
    column, block, and pull one output per rung — the dispatch+pull
    shape the production consume loop pays."""
    import jax
    import numpy as np

    from vlog_tpu.parallel.mesh import pad_batch

    (y, u, v), _ = pad_batch(prog.data, y, u, v)
    n = y.shape[0]
    qps = {name: np.full((n, clen), qp, np.int32)
           for name, _, _, qp in rungs}
    rc = {name: {"budget": np.float32(2000.0), "alpha": np.float32(0.0)}
          for name, _, _, _ in rungs}
    outs = prog.dispatch(y, u, v, qps, rc)
    jax.block_until_ready(outs)
    for name, _, _, _ in rungs:
        np.asarray(outs[name]["sse_y"])


def measure_mesh_shapes(devices, rungs, h: int, w: int, clen: int,
                        shapes=None, iters: int = 3) -> dict:
    """Chain-ladder throughput (frames/s) per 2-D (data × rung) mesh
    shape, on two workloads:

    - ``full``: one chain per data slot — each shape at its natural
      batch, measuring pure scale-out; and
    - ``small_batch``: 2 chains regardless of shape (n_chains <
      devices) — the workload where a data-only shape pads 2 chains up
      to its full width (every padded chain is discarded encode work)
      while a 2-D shape spends the same devices splitting rungs across
      columns instead.

    fps counts REAL frames only, so data-only padding waste shows up
    directly in the small_batch numbers. The default sweep is every
    data-only divisor shape (1x1/2x1/.../Nx1) plus the full-device 2-D
    shapes (N/r x r for each divisor r <= n_rungs)."""
    from vlog_tpu import config
    from vlog_tpu.parallel.ladder import ladder_chain_grid
    from vlog_tpu.parallel.mesh import MeshShape, rung_grid

    n_dev = len(devices)
    if shapes is None:
        divs = [d for d in range(1, n_dev + 1) if n_dev % d == 0]
        shapes = [(d, 1) for d in divs]
        shapes += [(n_dev // r, r) for r in divs if 1 < r <= len(rungs)]

    out: dict = {}
    for d, r in shapes:
        if d * r > n_dev or r > len(rungs):
            continue
        shape = MeshShape(d, r)
        grid = (rung_grid(rungs, shape, list(devices[:d * r]))
                if d * r > 1 else None)
        prog = ladder_chain_grid(rungs, h, w, search=4, grid=grid,
                                 deblock=config.H264_DEBLOCK)
        for workload, chains in (("full", d), ("small_batch", 2)):
            y, u, v = _chain_batch(7, chains, clen, h, w)
            _dispatch_grid(prog, rungs, y, u, v, clen)   # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                _dispatch_grid(prog, rungs, y, u, v, clen)
            dt = (time.perf_counter() - t0) / iters
            out.setdefault(workload, {})[shape.label] = round(
                chains * clen / dt, 2)

    small = out.get("small_batch", {})
    data_only = small.get(f"{n_dev}x1")
    two_d = {k: v for k, v in small.items() if not k.endswith("x1")}
    if data_only and two_d:
        best = max(two_d, key=lambda k: two_d[k])
        out["small_batch_summary"] = {
            "data_only_shape": f"{n_dev}x1", "data_only": data_only,
            "best_2d_shape": best, "best_2d": two_d[best],
            "win_x": round(two_d[best] / data_only, 2)}
    return out


def measure_scheduler_packing(devices, rungs, h: int, w: int, clen: int,
                              chains_per_job: int | None = None,
                              dispatches: int = 3) -> dict:
    """Two queued jobs, 2x4-chip slots vs serialized full-mesh runs.

    Each job's batch carries half-mesh-width chains — the shape where a
    full-mesh lease pads every dispatch 2x (devices idle between
    useful work) and two narrow slots fit exactly. Serialized mode runs
    the jobs back to back on work-conserving full-mesh leases;
    slotted mode admits both through the real scheduler so each leases
    a 4-chip slot and they run concurrently."""
    import threading

    from vlog_tpu import config
    from vlog_tpu.parallel.ladder import ladder_chain_program
    from vlog_tpu.parallel.mesh import make_mesh, pad_batch
    from vlog_tpu.parallel.scheduler import MeshScheduler

    n_dev = len(devices)
    if n_dev < 2:
        # One device = one slot: the two-party barrier below would
        # deadlock against the single grant. Nothing to pack.
        return {"skipped": "needs >= 2 devices for 2 slots"}
    slots = 2
    chains = chains_per_job or max(1, n_dev // 2)

    def prepare_job(lease, seed: int):
        """Build + compile this job's program on its lease's mesh;
        returns the timed dispatch loop (compile excluded from timing
        in BOTH modes)."""
        mesh = make_mesh("data:-1", devices=list(lease.devices)) \
            if lease.width > 1 else None
        fn, mats = ladder_chain_program(rungs, h, w, search=4, mesh=mesh,
                                        deblock=config.H264_DEBLOCK)
        y, u, v = _chain_batch(seed, chains, clen, h, w)
        if lease.width > 1:
            (y, u, v), _ = pad_batch(lease.width, y, u, v)
        _dispatch_chains(fn, mats, mesh, rungs, y, u, v, clen)  # compile

        def go() -> None:
            for _ in range(dispatches):
                _dispatch_chains(fn, mats, mesh, rungs, y, u, v, clen)
        return go

    # --- serialized: each job is alone, so the work-conserving
    # fallback hands it the FULL mesh; the queue runs behind it.
    sched = MeshScheduler(devices=list(devices), slots=slots)
    serial_s = 0.0
    serial_widths = []
    for seed in (11, 12):
        ticket = sched.admit()
        lease = ticket.acquire()
        serial_widths.append(lease.width)
        try:
            go = prepare_job(lease, seed)
            t0 = time.perf_counter()
            go()
            serial_s += time.perf_counter() - t0
        finally:
            ticket.close()

    # --- slotted: both jobs admitted before either acquires, so the
    # grant renegotiates to two narrow slots and they run concurrently;
    # a barrier aligns the timed regions after per-slot compiles.
    sched = MeshScheduler(devices=list(devices), slots=slots)
    tickets = [sched.admit() for _ in range(2)]
    barrier = threading.Barrier(2)
    slot_widths = []
    spans = []
    errors = []

    def slot_job(ticket, seed: int) -> None:
        try:
            lease = ticket.acquire()
            slot_widths.append(lease.width)
            try:
                go = prepare_job(lease, seed)
                barrier.wait()
                t0 = time.perf_counter()
                go()
                spans.append((t0, time.perf_counter()))
            finally:
                ticket.close()
        except BaseException as exc:  # noqa: BLE001 — surfaced below
            errors.append(exc)
            barrier.abort()

    threads = [threading.Thread(target=slot_job, args=(t, 21 + i),
                                name=f"vlog-dryrun-slot-{i}")
               for i, t in enumerate(tickets)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    slotted_s = max(t1 for _, t1 in spans) - min(t0 for t0, _ in spans)

    return {
        "jobs": 2,
        "chains_per_job_batch": chains,
        "dispatches_per_job": dispatches,
        "serial_widths": serial_widths,
        "slot_widths": sorted(slot_widths),
        "serial_full_mesh_s": round(serial_s, 3),
        "two_slot_s": round(slotted_s, 3),
        "speedup": round(serial_s / slotted_s, 3) if slotted_s else 0.0,
        "jobs_per_min_1slot": round(2 * 60.0 / serial_s, 2),
        "jobs_per_min_2slot": round(2 * 60.0 / slotted_s, 2),
    }


if __name__ == "__main__":
    run(int(sys.argv[1]) if len(sys.argv) > 1 else 8)
