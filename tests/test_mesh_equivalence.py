"""Production-path mesh equivalence: JaxBackend.run on the 8-device CPU
mesh must emit byte-identical output to a single-device run.

VERDICT round-2 weak #5: the bit-identical test covered
``sharded_ladder_levels`` but not the backend's batching/padding/QP
plumbing around it. Here the FULL pipeline (process_video ->
JaxBackend.run -> segments/playlists/manifests) runs once on this test
process's virtual 8-device mesh (conftest pins
``--xla_force_host_platform_device_count=8``) and once in a single-device
subprocess, and every published file is byte-compared.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

from tests.fixtures.media import make_y4m

_SINGLE_DEV_SCRIPT = """
import sys
import jax
assert len(jax.devices()) == 1, jax.devices()
from vlog_tpu import config
from vlog_tpu.worker.pipeline import process_video
kw = {}
mode = sys.argv[3]
if mode.endswith("+h265"):
    mode = mode[:-5]
    kw["codec"] = "h265"
if mode == "p":
    kw["rungs"] = (config.QualityRung("360p", 360, 0, 0, base_qp=30),)
process_video(sys.argv[1], sys.argv[2], audio=False, segment_duration_s=1.0,
              gop_mode=mode, **kw)
"""


def _tree_files(root: Path) -> dict[str, bytes]:
    # the rate-control resume journal is run state shaped by the
    # dispatch-batch (device-count) geometry; the byte-identity
    # contract covers published artifacts only (as does outputs.json)
    from vlog_tpu.storage.integrity import RC_JOURNAL_NAME

    return {
        str(p.relative_to(root)): p.read_bytes()
        for p in sorted(root.rglob("*"))
        if p.is_file() and p.name != RC_JOURNAL_NAME
    }


def _compare_runs(tmp_path, src, gop_mode: str, mesh_kwargs: dict):
    from vlog_tpu.worker.pipeline import process_video

    mesh_out = tmp_path / "mesh8"
    process_video(src, mesh_out, audio=False, segment_duration_s=1.0,
                  gop_mode=gop_mode.removesuffix("+h265"),
                  **({"codec": "h265"} if gop_mode.endswith("+h265") else {}),
                  **mesh_kwargs)

    single_out = tmp_path / "single"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.run(
        [sys.executable, "-c", _SINGLE_DEV_SCRIPT, str(src),
         str(single_out), gop_mode],
        env=env, cwd="/root/repo", timeout=600,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    assert proc.returncode == 0, proc.stdout[-3000:]
    mesh_files = _tree_files(mesh_out)
    single_files = _tree_files(single_out)
    assert set(mesh_files) == set(single_files), (
        set(mesh_files) ^ set(single_files))
    assert any(k.endswith(".m4s") for k in mesh_files)
    for rel, data in single_files.items():
        assert mesh_files[rel] == data, (
            f"{rel}: mesh output differs from single-device "
            f"({len(mesh_files[rel])} vs {len(data)} bytes)")


@pytest.mark.slow
def test_backend_run_on_mesh_matches_single_device_intra(tmp_path):
    """All-intra: byte identity must hold INCLUDING the closed-loop rate
    controller (frame-DP batching is device-count-invariant)."""
    import jax

    assert len(jax.devices()) == 8, "conftest must pin the 8-device mesh"
    src = make_y4m(tmp_path / "src.y4m", n_frames=20, width=128, height=96,
                   fps=10)
    _compare_runs(tmp_path, src, "intra", {})


@pytest.mark.slow
def test_backend_run_on_mesh_matches_single_device_chains(tmp_path):
    """I+P chains at constant QP: the compute (ME/MC/residual/entropy)
    must be byte-identical across device counts. Closed-loop rate control
    is excluded by design here — the mesh dispatches several chains per
    feedback step, so the QP *schedule* legitimately differs with device
    count; determinism of the compute is the invariant."""
    import jax

    from vlog_tpu import config

    assert len(jax.devices()) == 8
    src = make_y4m(tmp_path / "src.y4m", n_frames=30, width=128, height=96,
                   fps=10)
    rung = config.QualityRung("360p", 360, 0, 0, base_qp=30)  # constant QP
    _compare_runs(tmp_path, src, "p", {"rungs": (rung,)})


@pytest.mark.slow
def test_hevc_backend_run_on_mesh_matches_single_device(tmp_path):
    """Fused HEVC chain ladder: byte identity across device counts at
    constant QP (same invariant as the H.264 chain test — compute
    determinism; the QP *schedule* is rate-control-free here)."""
    import jax

    from vlog_tpu import config

    assert len(jax.devices()) == 8
    src = make_y4m(tmp_path / "src.y4m", n_frames=30, width=128, height=96,
                   fps=10)
    rung = config.QualityRung("360p", 360, 0, 0, base_qp=30)  # constant QP
    _compare_runs(tmp_path, src, "p+h265", {"rungs": (rung,)})


# --------------------------------------------------------------------------
# 2-D (data × rung) grid: byte identity across every mesh shape ×
# pipeline depth, h264 intra + chain and hevc, plus the small-batch
# workload the rung axis exists for (n_chains < data width).
# --------------------------------------------------------------------------

# Four constant-QP rungs (bitrate 0 -> no closed-loop rate feedback):
# chain batching legitimately varies with the data-axis width, so the
# shape-invariance contract needs a QP schedule that cannot depend on
# how many chains share a dispatch.
_RUNGS_2D = (("96p", 96, 30), ("64p", 64, 31),
             ("48p", 48, 32), ("32p", 32, 33))

# data:1,rung:8 exercises the clamp (4 rungs -> 1x4); the others are
# the full 8-device shapes. "auto" rides along in the chain test.
_SPECS_2D = ("data:1,rung:8", "data:2,rung:4",
             "data:4,rung:2", "data:8,rung:1")

_SINGLE_DEV_SCRIPT_2D = """
import sys
import jax
assert len(jax.devices()) == 1, jax.devices()
from vlog_tpu import config
from vlog_tpu.worker.pipeline import process_video
mode = sys.argv[3]
kw = {"rungs": tuple(
    config.QualityRung(n, h, 0, 0, base_qp=q)
    for n, h, q in (("96p", 96, 30), ("64p", 64, 31),
                    ("48p", 48, 32), ("32p", 32, 33)))}
if mode.endswith("+h265"):
    mode = mode[:-5]
    kw["codec"] = "h265"
process_video(sys.argv[1], sys.argv[2], audio=False, segment_duration_s=1.0,
              gop_mode=mode, **kw)
"""


def _rungs_2d(config):
    return tuple(config.QualityRung(n, h, 0, 0, base_qp=q)
                 for n, h, q in _RUNGS_2D)


def _single_device_tree_2d(tmp_path, src, gop_mode: str):
    single_out = tmp_path / "single"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.run(
        [sys.executable, "-c", _SINGLE_DEV_SCRIPT_2D, str(src),
         str(single_out), gop_mode],
        env=env, cwd="/root/repo", timeout=600,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    assert proc.returncode == 0, proc.stdout[-3000:]
    ref = _tree_files(single_out)
    assert any(k.endswith(".m4s") for k in ref)
    return ref


def _run_2d_matrix(tmp_path, monkeypatch, gop_mode: str,
                   extra_specs: tuple[str, ...] = ()):
    """Every mesh shape × pipeline depth must publish the byte tree the
    single-chip run publishes (identity to the baseline implies identity
    across all shapes/depths)."""
    import jax

    from vlog_tpu import config
    from vlog_tpu.worker.pipeline import process_video

    assert len(jax.devices()) == 8, "conftest must pin the 8-device mesh"
    src = make_y4m(tmp_path / "src.y4m", n_frames=24, width=128, height=96,
                   fps=10)
    ref = _single_device_tree_2d(tmp_path, src, gop_mode)

    kw: dict = {"rungs": _rungs_2d(config)}
    mode = gop_mode
    if mode.endswith("+h265"):
        mode = mode[:-5]
        kw["codec"] = "h265"
    for depth in (1, 2, 3):
        monkeypatch.setattr(config, "PIPELINE_DEPTH", depth)
        specs = _SPECS_2D + extra_specs if depth == 2 else _SPECS_2D
        for spec in specs:
            monkeypatch.setattr(config, "TPU_MESH_SPEC", spec)
            out = tmp_path / f"d{depth}_{spec.replace(':', '').replace(',', '-')}"
            process_video(src, out, audio=False, segment_duration_s=1.0,
                          gop_mode=mode, **kw)
            files = _tree_files(out)
            assert set(files) == set(ref), (depth, spec,
                                            set(files) ^ set(ref))
            for rel, data in ref.items():
                assert files[rel] == data, (
                    f"depth {depth} shape {spec}: {rel} differs "
                    f"({len(files[rel])} vs {len(data)} bytes)")


@pytest.mark.slow
def test_2d_shape_matrix_intra(tmp_path, monkeypatch):
    """All-intra over the full shape × depth matrix: the intra batch
    width (max(frame_batch, data) rounded to data) is 8 for every
    shape, so identity holds including the closed-loop batch plumbing."""
    _run_2d_matrix(tmp_path, monkeypatch, "intra")


@pytest.mark.slow
def test_2d_shape_matrix_chains(tmp_path, monkeypatch):
    """I+P chains at constant QP over the matrix, plus auto shape
    selection: chains-per-dispatch varies with the data width, but each
    chain's compute must not care which shape dispatched it."""
    _run_2d_matrix(tmp_path, monkeypatch, "p", extra_specs=("auto",))


@pytest.mark.slow
def test_2d_shape_matrix_hevc(tmp_path, monkeypatch):
    """Fused HEVC chain ladder over the matrix."""
    _run_2d_matrix(tmp_path, monkeypatch, "p+h265")


@pytest.mark.slow
def test_2d_small_batch_byte_identical(tmp_path, monkeypatch):
    """n_chains < data width — the workload the rung axis exists for
    (r04: device_pull_s at 96% of wall on padded data-only dispatches).
    12 frames at 6-frame chains = 2 chains: 8x1 pads 2 -> 8 chains,
    2x4 runs them unpadded with rungs split 4 ways. Both must publish
    the single-chip byte tree."""
    import jax

    from vlog_tpu import config
    from vlog_tpu.worker.pipeline import process_video

    assert len(jax.devices()) == 8
    src = make_y4m(tmp_path / "src.y4m", n_frames=12, width=128, height=96,
                   fps=10)
    rungs = _rungs_2d(config)

    trees = {}
    for spec in ("data:8,rung:1", "data:2,rung:4"):
        monkeypatch.setattr(config, "TPU_MESH_SPEC", spec)
        out = tmp_path / spec.replace(":", "").replace(",", "-")
        process_video(src, out, audio=False, segment_duration_s=0.6,
                      gop_mode="p", rungs=rungs)
        trees[spec] = _tree_files(out)
        assert any(k.endswith(".m4s") for k in trees[spec])
    a, b = trees.values()
    assert set(a) == set(b)
    for rel, data in a.items():
        assert b[rel] == data, f"{rel}: 2x4 differs from 8x1"


# --------------------------------------------------------------------------
# Mesh job scheduler (parallel/scheduler.py): slot-width byte identity,
# concurrent-vs-serialized equivalence, and chaos drain.
# --------------------------------------------------------------------------

def _narrow_lease(sched):
    """A width-(n/slots) lease: admit a second ticket so the grant
    renegotiates away from the work-conserving full mesh, then withdraw
    it."""
    t1, t2 = sched.admit(), sched.admit()
    lease = t1.acquire()
    t2.close()
    return t1, lease


@pytest.mark.slow
def test_slot_widths_4_and_8_byte_identical(tmp_path):
    """The same job on a 4-chip slot lease, on a full-mesh (width-8)
    lease, and with no scheduler at all must publish byte-identical
    trees — the mesh-equivalence invariant extended to slot submeshes
    (all-intra: identity must hold INCLUDING closed-loop rate
    control)."""
    import jax

    from vlog_tpu.parallel.scheduler import MeshScheduler
    from vlog_tpu.worker.pipeline import process_video

    assert len(jax.devices()) == 8
    src = make_y4m(tmp_path / "src.y4m", n_frames=20, width=128, height=96,
                   fps=10)

    ref_out = tmp_path / "nosched"
    process_video(src, ref_out, audio=False, segment_duration_s=1.0,
                  gop_mode="intra")
    ref_files = _tree_files(ref_out)
    assert any(k.endswith(".m4s") for k in ref_files)

    sched = MeshScheduler(devices=list(jax.devices()), slots=2)

    # width 4: a narrow slot lease
    t1, lease = _narrow_lease(sched)
    assert lease.width == 4
    with lease:
        process_video(src, tmp_path / "slot4", audio=False,
                      segment_duration_s=1.0, gop_mode="intra")
    t1.close()

    # width 8: the lone-job work-conserving full-mesh lease
    t_full = sched.admit()
    lease8 = t_full.acquire()
    assert lease8.width == 8
    with lease8:
        process_video(src, tmp_path / "slot8", audio=False,
                      segment_duration_s=1.0, gop_mode="intra")
    t_full.close()

    for label in ("slot4", "slot8"):
        files = _tree_files(tmp_path / label)
        assert set(files) == set(ref_files), label
        for rel, data in ref_files.items():
            assert files[rel] == data, (
                f"{label}/{rel}: differs from the unscheduled full-mesh "
                f"tree ({len(files[rel])} vs {len(data)} bytes)")


@pytest.mark.slow
def test_two_concurrent_slot_jobs_match_serialized(tmp_path):
    """Two jobs admitted to 2x4-chip slots concurrently publish the
    same trees as back-to-back full-pipeline runs (per-slot executors
    share one entropy pool; output must not care)."""
    import threading

    import jax

    from vlog_tpu.parallel.scheduler import MeshScheduler
    from vlog_tpu.worker.pipeline import process_video

    assert len(jax.devices()) == 8
    srcs = [make_y4m(tmp_path / f"src{i}.y4m", n_frames=12 + 4 * i,
                     width=128, height=96, fps=10) for i in range(2)]

    refs = []
    for i, src in enumerate(srcs):
        out = tmp_path / f"serial{i}"
        process_video(src, out, audio=False, segment_duration_s=1.0,
                      gop_mode="intra")
        refs.append(_tree_files(out))

    sched = MeshScheduler(devices=list(jax.devices()), slots=2)
    tickets = [sched.admit() for _ in range(2)]
    errors = []

    def job(i: int) -> None:
        try:
            lease = tickets[i].acquire()
            assert lease.width == 4, lease
            with lease:
                process_video(srcs[i], tmp_path / f"conc{i}", audio=False,
                              segment_duration_s=1.0, gop_mode="intra")
        except BaseException as exc:  # noqa: BLE001 — surfaced below
            errors.append(exc)
        finally:
            tickets[i].close()

    threads = [threading.Thread(target=job, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert sched.capacity() == 2
    for i, ref in enumerate(refs):
        conc = _tree_files(tmp_path / f"conc{i}")
        assert set(conc) == set(ref)
        for rel, data in ref.items():
            assert conc[rel] == data, f"job {i}: {rel} differs"


@pytest.mark.slow
def test_chaos_slot_job_death_frees_slot(tmp_path):
    """Kill one slot's job mid-flight: the other slot's job completes
    untouched, the dead job's slot frees, and the next (lone) job gets
    the full mesh back."""
    import threading

    import jax

    from vlog_tpu.parallel.scheduler import MeshScheduler
    from vlog_tpu.worker.pipeline import process_video

    assert len(jax.devices()) == 8
    srcs = [make_y4m(tmp_path / f"src{i}.y4m", n_frames=12, width=128,
                     height=96, fps=10) for i in range(2)]

    sched = MeshScheduler(devices=list(jax.devices()), slots=2)
    tickets = [sched.admit() for _ in range(2)]
    outcomes: dict[int, BaseException | str] = {}

    def doomed_cb(done, total, msg):
        raise RuntimeError("chaos: slot job killed mid-flight")

    def job(i: int) -> None:
        try:
            lease = tickets[i].acquire()
            with lease:
                process_video(srcs[i], tmp_path / f"out{i}", audio=False,
                              segment_duration_s=1.0, gop_mode="intra",
                              progress_cb=doomed_cb if i == 0 else None)
            outcomes[i] = "ok"
        except BaseException as exc:  # noqa: BLE001 — the assertion target
            outcomes[i] = exc
        finally:
            tickets[i].close()

    threads = [threading.Thread(target=job, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert isinstance(outcomes[0], RuntimeError)       # the kill landed
    assert outcomes[1] == "ok", outcomes[1]            # survivor finished
    survivor = _tree_files(tmp_path / "out1")
    assert any(k.endswith(".m4s") for k in survivor)
    assert "master.m3u8" in survivor

    # both slots are free again, and a lone newcomer renegotiates back
    # to the full mesh (the freed slot really returned to the pool)
    assert sched.capacity() == 2
    t_next = sched.admit()
    lease = t_next.acquire(timeout=5)
    assert lease.width == 8 and lease.is_full_mesh
    t_next.close()
