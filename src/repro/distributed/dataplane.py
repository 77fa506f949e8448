"""Partition-axis data plane: shard_map-parallel ingest and query eval.

The partition is the paper's unit of work — sketch construction and
per-partition query answers are embarrassingly parallel along the
partition axis — so the multi-device story is one sharding rule: bulk
tensors keep their single-device layout except the partition axis, which
is padded up to a multiple of the mesh size and sharded
(`NamedSharding(mesh, P(..., "part", ...))`).  Every kernel launch runs
under `shard_map` and sees only its local shard, which keeps the launched
programs *mesh-oblivious*: the same driver cores as the single-device
path (`queries/device.py`, `core/ingest.py`), traced at local-shard
shapes, with the same `kernels/telemetry.TraceRegistry` census
discipline.  Only the small per-partition result tensors (moments,
counts, answers) are gathered back to the host.

Correctness contract:

  * **Bit parity.**  Each partition's reductions stay on one device with
    unchanged shapes and fold order, so sharded results are bit-identical
    to the single-device device backend; a degenerate 1-device mesh is
    literally today's path behind one `shard_map`.
  * **Padding is masked, never aggregated.**  Padded partitions are
    all-zero and are sliced off by `gather` before anything reads them —
    P not divisible by the mesh size costs dead FLOPs, not correctness.
  * **Bounded compiles.**  `sharded_call` memoizes one jitted
    `shard_map` per (mesh, fn, specs, statics), and the census a workload
    implies has the same cardinality on every mesh size (local shapes
    differ, the key *set* does not grow with devices).

Mesh resolution order: explicit argument > ``REPRO_MESH`` env var
(`repro.backends.default_mesh_devices`) > no mesh.  Meshes are built by
`launch/mesh.py::make_data_plane_mesh` on the shared partition axis
(`distributed/axes.py::PARTITION_AXIS`).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.distributed.axes import PARTITION_AXIS
from repro.kernels.telemetry import TraceRegistry


@dataclasses.dataclass(frozen=True)
class PartitionPlane:
    """A 1-axis device mesh over the partition dimension.

    The handle every ``plane=`` argument accepts (`build_statistics`,
    `build_sketches`, `EvalCache`, `AnswerStore`): partition-axis tensors
    are zero-padded to a mesh multiple and sharded along the shared
    ``"part"`` axis (`shard_partitions`), launches run under `shard_map`
    via `sharded_call`, and per-partition results come back through
    `gather` with the pad sliced off.  Sharded results are bit-identical
    to the single-device path on every mesh size (each partition's
    reductions stay on one device with unchanged shapes and fold order),
    and the compile census is mesh-size-independent.  Obtain one via
    `resolve_plane` ("auto" = the ``REPRO_MESH`` policy, an int = that
    many devices, None = the single-device path).
    """

    mesh: jax.sharding.Mesh

    @property
    def num_devices(self) -> int:
        return int(self.mesh.shape[PARTITION_AXIS])

    def padded(self, num_partitions: int) -> int:
        """P rounded up to a multiple of the mesh size (shard_map needs
        equal local shards; the pad partitions are all-zero and masked)."""
        d = self.num_devices
        return -(-num_partitions // d) * d

    def local(self, num_partitions: int) -> int:
        """Partitions per device — the P every sharded launch sees."""
        return self.padded(num_partitions) // self.num_devices

    def shard_partitions(self, arr, axis: int = 0, target: int | None = None) -> jax.Array:
        """Zero-pad `axis` (the partition axis) to a mesh multiple and
        place the array sharded along it; everything else is replicated.

        ``target`` asks for extra zero slack beyond the mesh multiple (it
        is itself rounded up to one): the streaming ingest plane pads the
        device column stack to its shape *bucket* so in-place appends can
        write new partitions into the slack without changing the sharded
        shape (`queries.engine.EvalCache.device_stack`)."""
        arr = np.asarray(arr)
        pad = self.padded(max(arr.shape[axis], target or 0)) - arr.shape[axis]
        if pad:
            widths = [(0, 0)] * arr.ndim
            widths[axis] = (0, pad)
            arr = np.pad(arr, widths)
        spec = [None] * arr.ndim
        spec[axis] = PARTITION_AXIS
        return jax.device_put(arr, NamedSharding(self.mesh, PartitionSpec(*spec)))

    def gather(self, arr, num_partitions: int, axis: int = 0) -> np.ndarray:
        """Device result → host numpy with the pad partitions sliced off."""
        out = np.asarray(arr)
        sl = [slice(None)] * out.ndim
        sl[axis] = slice(0, num_partitions)
        return out[tuple(sl)]


# --------------------------------------------------------------------------
# mesh resolution (explicit arg > REPRO_MESH > off)
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def plane_of(num_devices: int) -> PartitionPlane:
    from repro.launch.mesh import make_data_plane_mesh

    return PartitionPlane(make_data_plane_mesh(num_devices))


def resolve_plane(plane="auto") -> PartitionPlane | None:
    """Normalize a plane spec: None → single-device path, "auto" → the
    ``REPRO_MESH`` policy, an int → that many devices, a Mesh or
    PartitionPlane passes through."""
    if plane is None:
        return None
    if isinstance(plane, PartitionPlane):
        return plane
    if isinstance(plane, jax.sharding.Mesh):
        return PartitionPlane(plane)
    if plane == "auto":
        from repro.backends import default_mesh_devices

        n = default_mesh_devices()
        return plane_of(n) if n else None
    if isinstance(plane, int):
        return plane_of(plane)
    raise ValueError(f"bad partition-plane spec {plane!r}")


# --------------------------------------------------------------------------
# memoized shard_map launches
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _sharded_jit(mesh, fn, in_specs, out_specs, static):
    body = functools.partial(fn, **dict(static)) if static else fn
    # bodies are purely shard-local (no collectives) and outputs declare
    # their partitioned axes explicitly, so varying-manual-axes checking
    # buys nothing and trips over primitives without rules (segment_sum)
    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)
    )


def sharded_call(plane: PartitionPlane, fn, in_specs, out_specs, static=()):
    """Jitted `shard_map` of a module-level fn, one executable per
    (mesh, fn, specs, statics) — the compile-census contract.  `fn` runs
    on local shards and must take its static parameters as keywords
    (passed here as a tuple of (name, value) pairs)."""
    return _sharded_jit(plane.mesh, fn, tuple(in_specs), out_specs, tuple(static))


# convenience specs: arrays whose only sharded axis is the partition axis
def partition_spec(rank: int, axis: int) -> PartitionSpec:
    spec = [None] * rank
    spec[axis] = PARTITION_AXIS
    return PartitionSpec(*spec)


REPLICATED = PartitionSpec()


# --------------------------------------------------------------------------
# streaming append: write new partitions into a buffer's reserved slack
# --------------------------------------------------------------------------
TRACES = TraceRegistry("dataplane")


@functools.lru_cache(maxsize=None)
def _write_jit(mesh, rank, axis):
    def body(buf, delta, start):
        TRACES.note("write_partitions", axis, *buf.shape, delta.shape[axis])
        idx = tuple(start if i == axis else 0 for i in range(rank))
        return jax.lax.dynamic_update_slice(buf, delta, idx)

    if mesh is None:
        return jax.jit(body)
    spec = [None] * rank
    spec[axis] = PARTITION_AXIS
    return jax.jit(body, out_shardings=NamedSharding(mesh, PartitionSpec(*spec)))


def write_partitions(buf: jax.Array, delta, start: int, axis: int = 0,
                     plane: PartitionPlane | None = None) -> jax.Array:
    """Write ``delta`` into ``buf`` at offset ``start`` along the partition
    axis — the O(delta) device-side append behind the streaming plane.

    ``buf`` keeps its (possibly sharded) shape: the caller must have
    reserved slack (`shard_partitions(target=)` / a padded shape bucket)
    so the delta fits.  Only the delta ships host→device; under a mesh the
    result stays sharded along the partition axis.

    The delta's partition count is zero-padded up to a power-of-two
    bucket when the padded write still fits the remaining slack (the
    slack being overwritten is zero anyway, and `dynamic_update_slice`
    would *clamp* an out-of-range start — shifting the write onto real
    partitions — so an oversized pad falls back to the exact shape).
    Varying-size appends therefore compile O(log slack) writes, not one
    per distinct size — `TRACES` counts them.
    """
    import jax.numpy as jnp

    from repro.core.clustering import bucket_size

    delta = np.asarray(delta)
    d = delta.shape[axis]
    if start + d > buf.shape[axis]:
        raise ValueError("append exceeds the buffer's reserved slack")
    db = bucket_size(d, minimum=1)
    if d and start + db <= buf.shape[axis] and db != d:
        widths = [(0, 0)] * delta.ndim
        widths[axis] = (0, db - d)
        delta = np.pad(delta, widths)
    f = _write_jit(None if plane is None else plane.mesh, buf.ndim, axis)
    return f(buf, jnp.asarray(delta), jnp.int32(start))
