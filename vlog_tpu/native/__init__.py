"""Native (C) host runtime: entropy coding hot loops.

The TPU owns the DSP; this package owns the serial bit-packing the host
must do per frame (CAVLC slice coding, NAL escaping). See build.py for
the on-demand toolchain story.
"""

from vlog_tpu.native.build import (  # noqa: F401
    NativeBuildError,
    get_lib,
    require_lib,
)
