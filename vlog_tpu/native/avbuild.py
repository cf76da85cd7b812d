"""Build + load the optional libav ingest shim (ctypes).

Separate from the entropy-coder build: this one links the system
libavformat/libavcodec/libswscale and is entirely optional — without the
headers/libraries, vlog_tpu keeps its first-party decode envelope and
foreign uploads are rejected at probe time, exactly like a reference
deployment without ffmpeg. Disable explicitly with VLOG_LIBAV=0.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path

_DIR = Path(__file__).parent
_BUILD = _DIR / "_build"
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False


class VtAvInfo(ctypes.Structure):
    _fields_ = [
        ("width", ctypes.c_int),
        ("height", ctypes.c_int),
        ("fps", ctypes.c_double),
        ("duration", ctypes.c_double),
        ("nb_frames", ctypes.c_int64),
        ("has_audio", ctypes.c_int),
        ("vcodec", ctypes.c_char * 32),
        ("acodec", ctypes.c_char * 32),
    ]


def _compile() -> Path:
    _BUILD.mkdir(exist_ok=True)
    from vlog_tpu.native.build import inputs_digest, publish, stamp_matches

    srcs = [_DIR / "avshim.c", _DIR / "av1enc.c"]
    so = _BUILD / "libvtav.so"
    cc = os.environ.get("CC", "gcc")
    digest = inputs_digest([*srcs, Path(__file__)], cc)   # flags live here
    if stamp_matches(so, digest):
        return so
    pid = os.getpid()
    tmp_so = _BUILD / f"libvtav.{pid}.so.tmp"
    cmd = [cc, "-O2", "-fPIC", "-shared", *map(str, srcs), "-o",
           str(tmp_so),
           "-lavformat", "-lavcodec", "-lavutil", "-lswscale"]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"avshim build failed: {proc.stderr[:1000]}")
    publish(tmp_so, so, digest)
    return so


def get_av_lib() -> ctypes.CDLL | None:
    """The loaded ingest shim, or None (unavailable/disabled)."""
    global _LIB, _TRIED
    if os.environ.get("VLOG_LIBAV", "1") in ("0", "false", "no"):
        return None
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        try:
            lib = ctypes.CDLL(str(_compile()))
        except (RuntimeError, OSError):
            _LIB = None
            return None
        lib.vt_av_open.restype = ctypes.c_void_p
        lib.vt_av_open.argtypes = [ctypes.c_char_p,
                                   ctypes.POINTER(VtAvInfo)]
        lib.vt_av_read.restype = ctypes.c_int64
        lib.vt_av_read.argtypes = [ctypes.c_void_p,
                                   ctypes.POINTER(ctypes.c_uint8),
                                   ctypes.c_int64]
        lib.vt_av_read_pts.restype = ctypes.c_int64
        lib.vt_av_read_pts.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_uint8),
                                       ctypes.POINTER(ctypes.c_double),
                                       ctypes.c_int64]
        lib.vt_av_seek.restype = ctypes.c_int
        lib.vt_av_seek.argtypes = [ctypes.c_void_p, ctypes.c_double]
        lib.vt_av_close.restype = None
        lib.vt_av_close.argtypes = [ctypes.c_void_p]
        lib.vt_av_audio_to_f32.restype = ctypes.c_int64
        lib.vt_av_audio_to_f32.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.vt_av1_open.restype = ctypes.c_void_p
        lib.vt_av1_open.argtypes = [ctypes.c_int, ctypes.c_int,
                                    ctypes.c_int, ctypes.c_int,
                                    ctypes.c_int64, ctypes.c_int,
                                    ctypes.c_int]
        lib.vt_av1_send.restype = ctypes.c_int
        lib.vt_av1_send.argtypes = [ctypes.c_void_p, u8p, u8p, u8p,
                                    ctypes.c_int]
        lib.vt_av1_flush.restype = ctypes.c_int
        lib.vt_av1_flush.argtypes = [ctypes.c_void_p]
        lib.vt_av1_receive.restype = ctypes.c_int64
        lib.vt_av1_receive.argtypes = [
            ctypes.c_void_p, u8p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int64)]
        lib.vt_av1_close.restype = None
        lib.vt_av1_close.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return _LIB
