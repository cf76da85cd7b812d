"""Device mesh construction (config: VLOG_TPU_MESH, e.g. "data:-1").

Two axes cover the media pipeline:

- ``data``: frames of a GOP batch (or I+P chains, or ASR audio windows)
  shard across it — all-intra frames, IDR-anchored chains and 30s ASR
  windows have no cross-item dependence, so data parallelism over ICI
  is free of steady-state collectives (SURVEY.md section 2d item 5).
- ``rung``: the ladder's quality rungs partition into cost-balanced
  COLUMN groups (:func:`balanced_rung_columns`) so each device column
  encodes only its own rung subset of the full frame batch. Source
  frames are replicated along this axis at staging time; each column's
  program stages only its own resize matrices, and each rung's d2h
  pull comes off its owning column, so the executor's async pulls
  parallelize across devices.

A 2-D ``("data", "rung")`` layout is resolved by
:func:`resolve_mesh_shape` (spec strings like ``data:2,rung:4``, or
``auto`` which picks the shape from batch size and rung count) and
realized by :func:`rung_grid` as per-column 1-D data submeshes — rungs
have heterogeneous output shapes, so the rung axis is a grid of
independent column programs rather than one SPMD program (which would
force every column to a common padded shape). The spec syntax still
allows other axes ("data:4,model:2") for the Whisper TP variant later;
the ladder grid ignores axes it does not know.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vlog_tpu import config


# The tree's one spelling of jax's shard_map: analysis/meshshim.py keeps
# every sharded program importing it from here.
shard_map = jax.shard_map


@dataclass(frozen=True)
class MeshSpec:
    axes: tuple[tuple[str, int], ...]   # (name, size); -1 = all remaining

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(n for n, _ in self.axes)


def parse_mesh_spec(spec: str | None = None) -> MeshSpec:
    spec = spec or config.TPU_MESH_SPEC
    axes = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition(":")
        axes.append((name.strip(), int(size) if size else -1))
    if not axes:
        axes = [("data", -1)]
    return MeshSpec(tuple(axes))


def make_mesh(spec: str | MeshSpec | None = None,
              devices: list | None = None) -> Mesh:
    """Build a Mesh from a spec string; -1 axes absorb remaining devices."""
    if not isinstance(spec, MeshSpec):
        spec = parse_mesh_spec(spec)
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    sizes = [s for _, s in spec.axes]
    wild = [i for i, s in enumerate(sizes) if s == -1]
    fixed = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
    if len(wild) > 1:
        raise ValueError(f"at most one -1 axis allowed in mesh spec {spec}")
    if wild:
        if n % fixed:
            raise ValueError(f"{n} devices not divisible by fixed axes {fixed}")
        sizes[wild[0]] = n // fixed
    total = int(np.prod(sizes))
    if total > n:
        raise ValueError(f"mesh {sizes} needs {total} devices, have {n}")
    arr = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(arr, spec.axis_names)


def shard_frames(mesh: Mesh, *arrays, axis: str = "data"):
    """Place (N, ...) arrays with N sharded over ``axis`` (rest replicated).

    N must divide by the axis size — callers pad GOP batches to the mesh
    (see pad_batch).
    """
    sharding = NamedSharding(mesh, P(axis))
    return tuple(jax.device_put(a, sharding) for a in arrays)


def pad_batch(n_devices: int, *arrays):
    """Edge-pad the leading (frame) axis up to a multiple of n_devices.

    Returns (padded_arrays, real_count). Padding frames are encode work
    that gets thrown away — bounded by n_devices-1 frames per flush.
    On a 2-D grid callers pass the DATA-axis width, not the device
    count: a ``2x4`` grid pads a small batch to 2 frames where the 1-D
    mesh padded it to 8.
    """
    n = arrays[0].shape[0]
    pad = (-n) % n_devices
    if pad == 0:
        return arrays, n
    out = []
    for a in arrays:
        reps = np.repeat(a[-1:], pad, axis=0)
        out.append(np.concatenate([a, reps], axis=0))
    return tuple(out), n


# --- 2-D (data × rung) grid layout ------------------------------------------

# Static description of one rung: (name, height, width, qp) — mirrored
# from parallel/ladder.py (redeclared here so mesh stays import-light).
RungSpecT = tuple[str, int, int, int]


@dataclass(frozen=True)
class MeshShape:
    """Resolved 2-D grid shape: ``data`` × ``rung`` device columns."""

    data: int
    rung: int

    @property
    def label(self) -> str:
        return f"{self.data}x{self.rung}"

    @property
    def n_devices(self) -> int:
        return self.data * self.rung


def balanced_rung_columns(rungs: tuple[RungSpecT, ...],
                          n_cols: int) -> tuple[tuple[int, ...], ...]:
    """Partition rung indices into ``n_cols`` pixel-rate-balanced groups.

    Greedy LPT by ``h*w`` (the resize+DSP cost is ~linear in pixel
    rate): the 2160p rung lands alone in one column while the small
    rungs stack up in another, so column wall times roughly equalize.
    Deterministic (ties break toward the lower column index) — the
    partition is part of the compiled-program cache key.
    """
    if not 1 <= n_cols <= len(rungs):
        raise ValueError(
            f"need 1 <= columns <= rungs, got {n_cols} cols, "
            f"{len(rungs)} rungs")
    order = sorted(range(len(rungs)),
                   key=lambda i: (-rungs[i][1] * rungs[i][2], i))
    loads = [0] * n_cols
    cols: list[list[int]] = [[] for _ in range(n_cols)]
    for i in order:
        j = min(range(n_cols), key=lambda c: (loads[c], c))
        cols[j].append(i)
        loads[j] += rungs[i][1] * rungs[i][2]
    return tuple(tuple(sorted(c)) for c in cols)


def _column_cost(rungs: tuple[RungSpecT, ...], n_cols: int) -> int:
    """Pixel rate of the heaviest column under the balanced partition."""
    cols = balanced_rung_columns(rungs, n_cols)
    return max(sum(rungs[i][1] * rungs[i][2] for i in col) for col in cols)


def auto_mesh_shape(n_devices: int, rungs: tuple[RungSpecT, ...],
                    batch_hint: int | None = None) -> MeshShape:
    """Pick the (data, rung) split from batch size and rung count.

    Scores every divisor pair ``d*r == n_devices`` (with ``r`` capped
    at the rung count) by a wall-clock model: the heaviest column's
    pixel rate times the number of data-axis passes the hinted batch
    needs (``ceil(hint/d)`` — padding a small batch to a wide data axis
    costs full passes). Ties prefer the wider data axis: with enough
    items per dispatch, pure data parallelism has the least staging
    replication.
    """
    n_rungs = max(1, len(rungs))
    hint = max(1, batch_hint or n_devices)
    best: tuple | None = None
    for d in range(1, n_devices + 1):
        if n_devices % d:
            continue
        r = n_devices // d
        if r > n_rungs:
            continue
        passes = -(-hint // d)
        cost = _column_cost(rungs, r) * passes if rungs else passes
        if best is None or (cost, -d) < (best[0], -best[1]):
            best = (cost, d, r)
    assert best is not None   # d == n_devices, r == 1 always qualifies
    return MeshShape(best[1], best[2])


def resolve_mesh_shape(spec: str | None, n_devices: int,
                       rungs: tuple[RungSpecT, ...],
                       batch_hint: int | None = None) -> MeshShape:
    """Resolve VLOG_TPU_MESH (or ``spec``) into a grid shape.

    ``auto`` defers to :func:`auto_mesh_shape`; otherwise the spec's
    ``data`` and ``rung`` axes are read (one may be ``-1``; unknown
    axes are ignored — they belong to non-ladder programs). The rung
    axis is clamped to the rung count (a freed wildcard data axis
    absorbs the remainder), and the product must fit the device set.
    """
    spec = (spec if spec is not None else config.TPU_MESH_SPEC).strip()
    n_rungs = max(1, len(rungs))
    if spec.lower() == "auto":
        return auto_mesh_shape(n_devices, rungs, batch_hint)
    sizes = dict(parse_mesh_spec(spec).axes)
    data = sizes.get("data", -1)
    rung = sizes.get("rung", 1)
    if data == -1 and rung == -1:
        raise ValueError(f"at most one -1 axis allowed in mesh spec {spec!r}")
    if rung != -1:
        rung = min(max(1, rung), n_rungs)
    if data == -1:
        data = max(1, n_devices // max(rung, 1))
    elif rung == -1:
        rung = min(n_rungs, max(1, n_devices // data))
    if data * rung > n_devices:
        raise ValueError(
            f"mesh spec {spec!r} needs {data * rung} devices, "
            f"have {n_devices}")
    return MeshShape(data, rung)


@dataclass(frozen=True)
class GridColumn:
    """One rung column: a 1-D data submesh + the rung subset it owns."""

    mesh: Mesh
    rungs: tuple[RungSpecT, ...]

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(r[0] for r in self.rungs)


@dataclass(frozen=True)
class RungGrid:
    """A resolved (data × rung) device grid for one ladder.

    ``columns[j]`` owns a contiguous ``data``-wide device block and a
    cost-balanced rung subset; staging replicates the source frames
    into every column (the "rung axis replication") while each column
    keeps only its own resize matrices. Hashable — grids key the
    compiled-program caches exactly like a Mesh does.
    """

    shape: MeshShape
    columns: tuple[GridColumn, ...]

    @property
    def data(self) -> int:
        return self.shape.data

    @property
    def label(self) -> str:
        return self.shape.label

    def column_of(self, rung_name: str) -> GridColumn:
        for col in self.columns:
            if rung_name in col.names:
                return col
        raise KeyError(rung_name)


def rung_grid(rungs: tuple[RungSpecT, ...], shape: MeshShape,
              devices: list | tuple) -> RungGrid:
    """Lay ``rungs`` out over ``devices`` as ``shape`` prescribes.

    Column ``j`` gets the contiguous device block
    ``devices[j*data:(j+1)*data]`` (contiguity keeps slot-lease blocks
    ICI-adjacent, same idiom as the slot partition) as a 1-D "data"
    mesh — even at width 1, so inputs/matrices commit to the owning
    device instead of the process default.
    """
    devices = list(devices)
    if shape.n_devices > len(devices):
        raise ValueError(f"grid {shape.label} needs {shape.n_devices} "
                         f"devices, have {len(devices)}")
    groups = balanced_rung_columns(rungs, shape.rung)
    cols = []
    for j, idxs in enumerate(groups):
        block = devices[j * shape.data:(j + 1) * shape.data]
        cols.append(GridColumn(
            mesh=Mesh(np.asarray(block), ("data",)),
            rungs=tuple(rungs[i] for i in idxs)))
    return RungGrid(shape=shape, columns=tuple(cols))
