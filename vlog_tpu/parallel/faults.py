"""Device-fault classification: which failures are the HARDWARE's fault.

The failure plane (PR 1) classifies attempts as transient / permanent /
worker_crash / stalled — all shapes where either the input or the worker
process is suspect. A sick accelerator is neither: an XLA runtime error
escaping the compute thread (HBM corruption, a halted core, a wedged
ICI link) says nothing about the job, and under the mesh scheduler
(PR 6) it poisons every job packed onto the same device mesh unless the
offending devices are taken out of rotation.

This module is the classification oracle the daemon and remote worker
consult before attributing a failed attempt:

- :func:`is_device_fault` — True for exceptions that originated in the
  device runtime (XLA/jaxlib error types by name, plus the
  status-prefixed message shapes the runtime raises as bare
  ``RuntimeError``). Input/codec errors (``ValueError``, ``OSError``,
  validation failures) never classify; they stay transient/permanent.
- :class:`SyntheticDeviceFault` — the XLA-shaped error the
  ``device.fault`` failpoint injects inside the compute thread, so chaos
  runs exercise exactly the classification path a real sick chip takes.

A device-fault attempt is requeued with ``FailureClass.DEVICE_FAULT``
and does **not** burn the job's attempt budget (jobs/claims.py): the job
was innocent, and charging it would dead-letter healthy work through a
bad chip. The scheduler quarantines the lease's devices and a periodic
probe (:meth:`MeshScheduler.probe_quarantined`) reinstates them once
they compute again.

The type names cannot tell a sick chip from a program the chip cannot
hold. Established on a TPU v5 lite under jax 0.9.0 (PR 21): a ladder
program whose buffers exceed HBM fails its *compile* with
``jax.errors.JaxRuntimeError: RESOURCE_EXHAUSTED: Allocation (size=...)
would exceed memory (size=17179869184) :: ... space=hbm`` — the same
class a halted core raises — while an eager allocation past the limit
raises a plain ``ValueError: RESOURCE_EXHAUSTED: Error allocating
device buffer`` and never classifies at all. The first is a property of
(program, chip size) and repeats identically forever, so refunding it
quarantines the only chip and re-queues the job until the refund bound
while a waiter sees a hang. :class:`RepeatFaultDetector` is the rule
that ends it: the same device-classified error, from the same job, on
devices that pass the probe right now, is the job's own (permanent)
failure.
"""

from __future__ import annotations

import re
from collections import OrderedDict
from typing import Callable, Iterable

from vlog_tpu.utils import failpoints

__all__ = ["RepeatFaultDetector", "SyntheticDeviceFault",
           "is_device_fault", "maybe_inject_device_fault", "probe_device"]

# Exception type NAMES (not imports: jaxlib's error classes move between
# versions and must not become a hard dependency of the job plane).
_DEVICE_ERROR_TYPES = frozenset({
    "XlaRuntimeError",       # jaxlib.xla_extension — the usual carrier
    "JaxRuntimeError",
    "InternalError",
    "DataLossError",
    "ResourceExhaustedError",
    "UnavailableError",
})

# Message shapes the runtime raises as bare RuntimeError. Matched only
# on RuntimeError-family exceptions so an input error whose *text*
# mentions a device (e.g. a probe naming a file "device.mp4") cannot
# classify.
_DEVICE_MESSAGE_PATTERNS = (
    "internal: failed to execute",       # XLA Runtime executable errors
    "data_loss:",
    "resource_exhausted:",
    "unavailable:",
    "device halted",
    "hbm",                               # HBM OOM / corruption reports
    "out of memory while trying to allocate",
    "tpu driver",
    "device or resource busy",
    "slice_index out of bounds",         # ICI/slice topology faults
)


class SyntheticDeviceFault(RuntimeError):
    """The ``device.fault`` failpoint's payload: an XLA-shaped runtime
    error raised inside the compute thread, classified exactly like a
    real device fault (see :func:`is_device_fault`)."""


def is_device_fault(exc: BaseException) -> bool:
    """Did this failure originate in the accelerator runtime?

    Walks the ``__cause__``/``__context__`` chain (bounded) so a device
    error wrapped by pipeline plumbing still classifies. Deliberately
    conservative: only known runtime error type names, or RuntimeErrors
    carrying the runtime's status-prefixed message shapes, qualify.
    """
    seen = 0
    cur: BaseException | None = exc
    while cur is not None and seen < 8:
        if isinstance(cur, SyntheticDeviceFault):
            return True
        if isinstance(cur, failpoints.FailpointError):
            # a *different* armed failpoint (claims.*, backend.*) is an
            # injected plumbing fault, never a device fault
            return False
        name = type(cur).__name__
        if name in _DEVICE_ERROR_TYPES:
            return True
        if isinstance(cur, RuntimeError):
            msg = str(cur).lower()
            if any(p in msg for p in _DEVICE_MESSAGE_PATTERNS):
                return True
        seen += 1
        cur = cur.__cause__ or cur.__context__
    return False


def probe_device(device) -> bool:
    """The cheap health probe: put a tiny array on the device, reduce
    it, pull the result. Anything a sick chip does wrong — allocation,
    dispatch, the d2h pull — fails it (a raising probe counts as
    failing wherever it is used: quarantine reinstatement in
    :meth:`MeshScheduler.probe_quarantined`, and the repeat rule
    below)."""
    import jax
    import jax.numpy as jnp

    x = jax.device_put(jnp.arange(8, dtype=jnp.float32), device)
    return float(jax.block_until_ready(x).sum()) == 28.0


# what varies between two raises of the SAME runtime failure: pointers,
# buffer ids, byte counts
_VOLATILE = re.compile(r"0x[0-9a-f]+|\d+")


class RepeatFaultDetector:
    """One worker's memory of device-classified failures, by job.

    ``repeats_on_healthy_devices`` is asked once per device-classified
    failure. The first time a job raises a given error it answers False
    (the hardware gets the benefit of the doubt: refund, quarantine).
    When the same job raises the same error again — digits and
    addresses aside — it probes ``devices``; if every one computes, the
    chip is demonstrably fine and the failure is the program's:
    True, and the caller fails the job permanently instead of
    refunding it. A probe that fails or raises keeps the verdict at
    "hardware". Bounded: remembers the last ``capacity`` jobs.
    """

    def __init__(self, probe: Callable[[object], bool] = probe_device,
                 capacity: int = 256):
        self._probe = probe
        self._capacity = capacity
        self._seen: OrderedDict[int, str] = OrderedDict()

    @staticmethod
    def signature(exc: BaseException) -> str:
        return _VOLATILE.sub("#", f"{type(exc).__name__}: {exc}")[:2000]

    def repeats_on_healthy_devices(self, job_id: int, exc: BaseException,
                                   devices: Iterable) -> bool:
        sig = self.signature(exc)
        prior = self._seen.get(job_id)
        self._seen[job_id] = sig
        self._seen.move_to_end(job_id)
        while len(self._seen) > self._capacity:
            self._seen.popitem(last=False)
        if prior != sig:
            return False
        try:
            return all(self._probe(d) for d in devices)
        except Exception:  # noqa: BLE001 — a raising probe is a failing
            return False   # probe: the hardware stays the suspect


def maybe_inject_device_fault() -> None:
    """The ``device.fault`` failpoint site (compute thread, start of the
    backend ladder run). Armed, it raises a :class:`SyntheticDeviceFault`
    whose message mirrors a real XLA halt — so the whole quarantine /
    requeue / probe loop is drivable from ``VLOG_FAILPOINTS``."""
    try:
        failpoints.hit("device.fault")
    except failpoints.FailpointError as exc:
        raise SyntheticDeviceFault(
            "INTERNAL: Failed to execute XLA Runtime executable: run "
            "backend error: device halted (synthetic device.fault)"
        ) from exc
