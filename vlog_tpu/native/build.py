"""Build + load the native entropy-coding library (ctypes).

Compiled on demand with the system toolchain into
``vlog_tpu/native/_build/`` and reused only while a stamp file beside
the library records the sha256 of the exact inputs it was built from
(:func:`inputs_digest`) — ``_build/`` is git-ignored but travels with
a copied tree, and a library built from other sources must never be
loaded in place of the committed ones. No pip/pybind11 required
(environment constraint); pure C ABI via ctypes. All entry points
release the GIL for the duration of the call (ctypes semantics), so
the worker's per-frame thread pool scales across cores.

``VLOG_NATIVE=0`` selects the Python coders on purpose. With it unset a
failed build is an error on the backend path (:func:`require_lib`); the
per-slice helpers still treat a missing library as "use Python", which
is what the bit-identity tests of the two coders rely on.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path

_DIR = Path(__file__).parent
_BUILD = _DIR / "_build"
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False


class NativeBuildError(RuntimeError):
    pass


def inputs_digest(paths: list[Path], *extra: str) -> str:
    """sha256 over the named files' names and bytes (plus ``extra``
    strings such as the compiler command) — the build stamp."""
    h = hashlib.sha256()
    for p in paths:
        h.update(p.name.encode() + b"\0" + p.read_bytes() + b"\0")
    for e in extra:
        h.update(e.encode() + b"\0")
    return h.hexdigest()


def stamp_matches(so: Path, digest: str) -> bool:
    """Was ``so`` built from exactly the inputs ``digest`` names?"""
    stamp = so.with_suffix(".stamp")
    return (so.exists() and stamp.exists()
            and stamp.read_text().strip() == digest)


def publish(tmp_so: Path, so: Path, digest: str) -> None:
    """Atomically install a fresh build and then its stamp. A crash
    between the two leaves a library without a matching stamp, which
    the next process rebuilds."""
    stamp = so.with_suffix(".stamp")
    stamp.unlink(missing_ok=True)
    os.replace(tmp_so, so)
    tmp_stamp = stamp.with_suffix(f".stamp.{os.getpid()}.tmp")
    tmp_stamp.write_text(digest + "\n")
    os.replace(tmp_stamp, stamp)


def _compile() -> Path:
    _BUILD.mkdir(exist_ok=True)
    src = _DIR / "cavlc.c"
    jpeg_src = _DIR / "jpeg_pack.c"
    hevc_src = _DIR / "hevc_cabac.c"
    h264c_src = _DIR / "h264_cabac_enc.c"
    engine_hdr = _DIR / "cabac_engine.h"
    so = _BUILD / "libvtnative.so"
    from vlog_tpu.codecs.h264 import cabac_ctx_tables, cavlc_tables
    from vlog_tpu.codecs.hevc import tables as hevc_tables

    cc = os.environ.get("CC", "g++")
    digest = inputs_digest(
        [src, jpeg_src, hevc_src, h264c_src, engine_hdr,
         _DIR / "gen_tables.py",
         _DIR / "gen_hevc_tables.py",
         _DIR / "gen_h264_cabac_tables.py",
         Path(cavlc_tables.__file__),   # real inputs of the
         Path(hevc_tables.__file__),    # generators
         Path(cabac_ctx_tables.__file__),
         Path(__file__)], cc)           # the compiler flags live here
    if stamp_matches(so, digest):
        return so
    from vlog_tpu.native.gen_tables import generate

    # Per-process scratch names: multiple worker processes may race the
    # first build; each builds privately and os.replace publishes
    # atomically (last writer wins, all writers produce identical bits).
    from vlog_tpu.native.gen_h264_cabac_tables import (
        generate_c_header as gen_h264_hdr)
    from vlog_tpu.native.gen_hevc_tables import generate_c_header

    pid = os.getpid()
    inc = _BUILD / f"cavlc_tables.{pid}.inc"
    inc.write_text(generate())
    hevc_inc = _BUILD / f"hevc_tables.{pid}.inc"
    hevc_inc.write_text(generate_c_header())
    h264c_inc = _BUILD / f"h264_cabac_tables.{pid}.inc"
    h264c_inc.write_text(gen_h264_hdr())
    tmp_so = _BUILD / f"libvtnative.{pid}.so.tmp"
    cmd = [cc, "-O3", "-fPIC", "-shared", "-x", "c++",
           f"-DVT_TABLES_INC=\"{inc.name}\"",
           f"-DVT_HEVC_TABLES_INC=\"{hevc_inc.name}\"",
           f"-DVT_H264_CABAC_INC=\"{h264c_inc.name}\"",
           str(src), str(jpeg_src), str(hevc_src), str(h264c_src),
           "-I", str(_BUILD), "-I", str(_DIR), "-o", str(tmp_so)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True)
    except OSError as exc:          # no compiler on PATH
        raise NativeBuildError(f"native build failed: {exc}") from exc
    if proc.returncode != 0:
        raise NativeBuildError(f"native build failed: {proc.stderr[:2000]}")
    publish(tmp_so, so, digest)
    inc.rename(_BUILD / "cavlc_tables.inc")        # for reference/debugging
    hevc_inc.rename(_BUILD / "hevc_tables.inc")
    h264c_inc.rename(_BUILD / "h264_cabac_tables.inc")
    return so


_ERROR: Exception | None = None


def native_disabled() -> bool:
    """``VLOG_NATIVE=0``: the operator asked for the Python coders."""
    return os.environ.get("VLOG_NATIVE", "1") in ("0", "false", "no")


def require_lib() -> ctypes.CDLL | None:
    """:func:`get_lib` for the production path: None only when
    ``VLOG_NATIVE=0`` asked for the Python coders; a build or load
    failure raises instead of quietly running ~100x slower."""
    lib = get_lib()
    if lib is None and not native_disabled():
        raise NativeBuildError(
            "native entropy coders unavailable and VLOG_NATIVE is not 0: "
            f"{_ERROR}") from _ERROR
    return lib


def get_lib() -> ctypes.CDLL | None:
    """The loaded library, or None (build failure / disabled)."""
    global _LIB, _TRIED, _ERROR
    if native_disabled():
        return None
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        try:
            so = _compile()
            lib = ctypes.CDLL(str(so))
        except (NativeBuildError, OSError) as exc:
            _ERROR = exc
            _LIB = None
            return None
        i8 = ctypes.POINTER(ctypes.c_uint8)
        i32 = ctypes.POINTER(ctypes.c_int32)
        lib.vt_cavlc_encode_slice.restype = ctypes.c_int64
        lib.vt_cavlc_encode_slice.argtypes = [
            i32, i32, i32, i32,                      # levels arrays
            ctypes.c_int, ctypes.c_int,              # mbh, mbw
            i8, ctypes.c_int64,                      # header bytes
            ctypes.c_uint32, ctypes.c_int,           # header tail bits
            i32,                                     # nz scratch
            i8, ctypes.c_int64,                      # out buffer
        ]
        lib.vt_escape_emulation.restype = ctypes.c_int64
        lib.vt_escape_emulation.argtypes = [i8, ctypes.c_int64, i8]
        lib.vt_cavlc_encode_p_slice.restype = ctypes.c_int64
        lib.vt_cavlc_encode_p_slice.argtypes = [
            i32, i32, i32, i32,                      # luma, cdc, cac, mv
            ctypes.c_int, ctypes.c_int,              # mbh, mbw
            i8, ctypes.c_int64,                      # header bytes
            ctypes.c_uint32, ctypes.c_int,           # header tail bits
            i32,                                     # scratch
            i8, ctypes.c_int64,                      # out buffer
        ]
        lib.vt_h264_cabac_i_slice.restype = ctypes.c_int64
        lib.vt_h264_cabac_i_slice.argtypes = [
            i32, i32, i32, i32,                      # level arrays
            ctypes.c_int, ctypes.c_int,              # mbh, mbw
            ctypes.c_int,                            # slice qp
            i8, ctypes.c_int64,                      # header bytes
            i32,                                     # scratch
            i8, ctypes.c_int64,                      # out buffer
        ]
        lib.vt_h264_cabac_p_slice.restype = ctypes.c_int64
        lib.vt_h264_cabac_p_slice.argtypes = [
            i32, i32, i32, i32,                      # luma, cdc, cac, mv
            ctypes.c_int, ctypes.c_int,              # mbh, mbw
            ctypes.c_int,                            # slice qp
            i8, ctypes.c_int64,                      # header bytes
            i32,                                     # scratch
            i8, ctypes.c_int64,                      # out buffer
        ]
        i16 = ctypes.POINTER(ctypes.c_int16)
        lib.vt_hevc_encode_slice.restype = ctypes.c_int64
        lib.vt_hevc_encode_slice.argtypes = [
            i16, i16, i16,                           # luma, cb, cr levels
            ctypes.c_int32, ctypes.c_int32,          # rows, cols
            ctypes.c_int32,                          # slice qp
            i8, ctypes.c_int64,                      # out buffer
        ]
        lib.vt_hevc_encode_p_slice.restype = ctypes.c_int64
        lib.vt_hevc_encode_p_slice.argtypes = [
            i16, i16, i16,                           # luma, cb, cr levels
            i32,                                     # mv (y, x) int pels
            ctypes.c_int32, ctypes.c_int32,          # rows, cols
            ctypes.c_int32,                          # slice qp
            i32,                                     # mv scratch
            i8, ctypes.c_int64,                      # out buffer
        ]
        u16 = ctypes.POINTER(ctypes.c_uint16)
        lib.vt_jpeg_pack_scan.restype = ctypes.c_int64
        lib.vt_jpeg_pack_scan.argtypes = [
            i32, i8, ctypes.c_int64,                 # blocks, comp, n
            u16, i8, u16, i8, u16, i8, u16, i8,      # 4 Huffman tables
            i8, ctypes.c_int64,                      # out buffer
        ]
        _LIB = lib
        return _LIB
