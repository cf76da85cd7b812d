"""Accelerator backends (the hwaccel.py analog, SURVEY.md section 7 step 3).

Importing this package registers the built-in JAX backend; additional
backends register themselves via :func:`register_backend`.
"""

from vlog_tpu.backends.base import (  # noqa: F401
    Backend,
    Capabilities,
    ExecutionPlan,
    PlannedRung,
    RungResult,
    RunResult,
    available_backends,
    get_backend,
    plan_rung_geometry,
    register_backend,
    require_accelerator,
    select_backend,
)
from vlog_tpu.backends.source import (  # noqa: F401
    FrameSource,
    Mp4H264FrameSource,
    UnsupportedSource,
    Y4mFrameSource,
    open_source,
)
from vlog_tpu.backends import jax_backend  # noqa: F401  (registers "jax")
