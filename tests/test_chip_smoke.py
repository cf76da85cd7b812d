"""Bring-up gates (PR 21): nothing on the main path hides the device.

All CPU, all tier-1. The smoke's driver function runs end to end at a
tiny size with the platform check passed in as an argument; the entry
points a TPU deployment starts (`chip_smoke.py`, `bench.py`, the worker
daemons) refuse to run without a TPU; the compile cache is placed from
outside; the native build stamp follows content, not mtimes.
"""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from vlog_tpu import config

REPO = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cmd, **env):
    return subprocess.run(
        [sys.executable, *cmd], cwd=str(REPO), capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


# --------------------------------------------------------------------------
# The smoke's driver, end to end at a tiny size
# --------------------------------------------------------------------------

def test_smoke_driver_end_to_end_tiny(tmp_path, tiny_model_dir, monkeypatch):
    """Two rungs from a 144p source + the conftest tiny Whisper through
    the real ``WorkerDaemon.run()``: queue drains, tree/playlist/PSNR/
    captions checks hold, observations are filled in."""
    from vlog_tpu.asr.engine import reset_engine

    smoke = _load_smoke()
    ladder = (config.QualityRung("144p", 144, 400_000, 64_000, base_qp=30),
              config.QualityRung("72p", 72, 150_000, 64_000, base_qp=32))
    monkeypatch.setattr(config, "QUALITY_LADDER", ladder)
    monkeypatch.setattr(config, "LADDER_BY_NAME", {r.name: r for r in ladder})
    work = tmp_path / "work"
    work.mkdir()
    try:
        obs = smoke.run_smoke(work, require_platform="cpu", src_h=144,
                              src_w=256, chains=1,
                              whisper_dir=tiny_model_dir, wait_s=600.0)
    finally:
        reset_engine()
    assert obs["device"]["platform"] == "cpu"
    assert obs["entropy_native"] is True
    assert obs["resize_plane"] == {"144p": "identity", "72p": "xla"}
    assert obs["asr"]["windows_decoded"] == 3
    assert set(obs["decoded_psnr_y"]) == {"144p", "72p"}
    assert all(v >= smoke.PSNR_FLOOR_DB for v in obs["decoded_psnr_y"].values())
    assert set(obs["job_wall_s"]) == {"transcription", "transcode", "sprite"}
    assert obs["stage_s"]["compute_wait_s"] > 0
    # the conftest mesh: every virtual device in one data-parallel grid
    assert obs["mesh"]["mesh.shape"] == f"{obs['device']['count']}x1"
    assert obs["backend_compile_s"] > 0
    assert obs["compile_cache_dir"] is None        # CPU: no cache


def test_smoke_refuses_wrong_platform(tmp_path):
    smoke = _load_smoke()
    with pytest.raises(smoke.SmokeFailure, match="needs platform 'tpu'"):
        smoke.run_smoke(tmp_path)                  # default: require a TPU
    assert list(tmp_path.iterdir()) == []          # refused before any work


def test_smoke_vtt_reader():
    smoke = _load_smoke()
    cues = smoke.parse_vtt("WEBVTT\n\n00:00:00.000 --> 00:00:02.500\nhi\n\n"
                           "7\n00:00:02.500 --> 00:01:00.000\nthere\nyou\n")
    assert cues == [(0.0, 2.5, "hi"), (2.5, 60.0, "there\nyou")]
    assert smoke.parse_vtt("WEBVTT\n") == []
    for bad in ("", "00:00:00.000 --> 00:00:01.000\nx\n",
                "WEBVTT\n\nnot a cue\n",
                "WEBVTT\n\n00:00:05.000 --> 00:00:01.000\nx\n"):
        with pytest.raises(smoke.SmokeFailure):
            smoke.parse_vtt(bad)


# --------------------------------------------------------------------------
# Entry points refuse to run without a TPU
# --------------------------------------------------------------------------

def test_chip_smoke_and_bench_exit_nonzero_on_cpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0 and r.stdout.strip() == "", (r.stdout, r.stderr)
    assert "needs a TPU" in r.stderr
    r = _run(["bench.py"])
    assert r.returncode != 0 and r.stdout.strip() == "", (r.stdout, r.stderr)
    assert "no record" in r.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """Without the rest of the repo the script must fail, not pass."""
    (tmp_path / "chip_smoke.py").write_bytes(
        (REPO / "chip_smoke.py").read_bytes())
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=str(tmp_path),
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_daemon_entry_refuses_tpu_on_cpu(tmp_path):
    db = tmp_path / "never.db"
    r = _run(["-m", "vlog_tpu.worker.daemon", "--accelerator", "tpu",
              "--db", f"sqlite:///{db}"], VLOG_BASE_DIR=str(tmp_path / "d"))
    assert r.returncode != 0
    assert "refusing to start: --accelerator tpu" in r.stderr
    assert not db.exists() and not (tmp_path / "d").exists()   # before claiming


def test_require_accelerator_and_select_backend_propagate(monkeypatch):
    from vlog_tpu.backends import base, require_accelerator, select_backend

    be = select_backend()
    caps = require_accelerator(be, "cpu")            # a CPU worker: fine
    assert caps.device_kind == "cpu"
    assert caps.details["jax_device_kind"]           # as JAX names the chip
    with pytest.raises(SystemExit, match="no TPU"):
        require_accelerator(be, "tpu")

    class Broken:
        name = "broken"

        def detect(self):
            raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(base, "_REGISTRY", {"broken": Broken})
    monkeypatch.setattr(base, "_SELECTED", None)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        select_backend()
    # ...but it is only skipped while another backend answers
    monkeypatch.setattr(base, "_REGISTRY",
                        {"broken": Broken, "jax": type(be)})
    assert select_backend().name == "jax"


def test_remote_entry_refuses_before_registering(monkeypatch):
    import argparse
    import asyncio

    from vlog_tpu.worker import remote

    called = []

    async def register(*a, **k):
        called.append(a)
        return "key"

    monkeypatch.setattr(remote.WorkerAPIClient, "register", register)
    args = argparse.Namespace(
        api="http://127.0.0.1:1", key="", admin_secret="", name="r",
        work_dir="/nonexistent", accelerator="tpu", kinds="transcode",
        backend="", no_backend=False, whisper_dir=None)
    with pytest.raises(SystemExit, match="refusing to start"):
        asyncio.run(remote._amain(args))
    assert called == []


# --------------------------------------------------------------------------
# Compile cache: placed from outside
# --------------------------------------------------------------------------

def test_compile_cache_default_on_an_accelerator(tmp_path, monkeypatch):
    """Off CPU and with the variable unset the one fixed default is
    armed (the variable-set and CPU cases: tests/test_raw_speed.py
    ``test_compile_cache_policy``)."""
    import jax

    from vlog_tpu.parallel import compile_cache as cc

    class FakeTpu:
        platform = "tpu"

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", tmp_path / "_xla_cache")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        cc.reset_for_tests()
        assert cc.ensure_compile_cache() == str(tmp_path / "_xla_cache")
        assert (tmp_path / "_xla_cache").is_dir()
        assert updates == [
            ("jax_compilation_cache_dir", str(tmp_path / "_xla_cache")),
            ("jax_persistent_cache_min_compile_time_secs", 0.0)]
    finally:
        cc.reset_for_tests()
    # ...and that default is independent of cwd / BASE_DIR, git-ignored,
    # with no knob of our own beside jax's variable
    monkeypatch.undo()
    assert cc.DEFAULT_CACHE_DIR == REPO / "vlog_tpu" / "_xla_cache"
    assert "vlog_tpu/_xla_cache/" in (REPO / ".gitignore").read_text()
    assert not hasattr(config, "COMPILE_CACHE_DIR")


# --------------------------------------------------------------------------
# Native build: the stamp is a content hash
# --------------------------------------------------------------------------

def test_native_stamp_follows_content_not_mtime(tmp_path):
    from vlog_tpu.native import build

    src = tmp_path / "coder.c"
    src.write_text("int f(void) { return 1; }\n")
    so = tmp_path / "lib.so"
    digest = build.inputs_digest([src], "g++")
    assert not build.stamp_matches(so, digest)      # nothing built yet
    (tmp_path / "lib.tmp").write_bytes(b"\x7fELF-from-src-v1")
    build.publish(tmp_path / "lib.tmp", so, digest)
    assert build.stamp_matches(so, digest)
    # a touched mtime (a copied tree, a fresh checkout) changes nothing
    os.utime(src, (2_000_000_000, 2_000_000_000))
    assert build.stamp_matches(so, build.inputs_digest([src], "g++"))
    # a changed byte, or another compiler, is another build
    src.write_text("int f(void) { return 2; }\n")
    assert not build.stamp_matches(so, build.inputs_digest([src], "g++"))
    src.write_text("int f(void) { return 1; }\n")
    assert not build.stamp_matches(so, build.inputs_digest([src], "clang++"))
    # a library that arrived without its stamp (built elsewhere) is rebuilt
    so.with_suffix(".stamp").unlink()
    assert not build.stamp_matches(so, digest)


def test_native_build_reuses_only_a_matching_library(tmp_path, monkeypatch):
    """The real build: a second call reuses the stamped library without
    invoking the compiler; a library whose stamp names other sources (a
    ``_build/`` copied in with the tree) is rebuilt; no compiler and no
    ``VLOG_NATIVE=0`` is an error, not the Python coder."""
    from vlog_tpu.native import build

    monkeypatch.setattr(build, "_BUILD", tmp_path / "_build")
    so = build._compile()
    first = so.read_bytes()
    assert build.stamp_matches(so, so.with_suffix(".stamp").read_text().strip())

    calls = []
    real_run = subprocess.run

    def counting_run(cmd, *a, **k):
        calls.append(cmd[0])
        return real_run(cmd, *a, **k)

    monkeypatch.setattr(build.subprocess, "run", counting_run)
    os.utime(so, (1, 1))                     # older than every source
    assert build._compile() == so and calls == []
    so.write_bytes(b"built from something else")
    so.with_suffix(".stamp").write_text("0" * 64 + "\n")
    assert build._compile() == so and len(calls) == 1
    assert so.read_bytes() == first          # from the committed sources

    # gcc absent
    monkeypatch.setenv("CC", str(tmp_path / "no-such-compiler"))
    monkeypatch.setattr(build, "_TRIED", False)
    monkeypatch.setattr(build, "_LIB", None)
    monkeypatch.setattr(build, "_ERROR", None)
    monkeypatch.delenv("VLOG_NATIVE", raising=False)
    with pytest.raises(build.NativeBuildError, match="VLOG_NATIVE is not 0"):
        build.require_lib()
    monkeypatch.setenv("VLOG_NATIVE", "0")   # asked for: fine, no library
    assert build.require_lib() is None
