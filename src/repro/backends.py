"""Execution-backend policy for the offline plane (ingest + query eval).

Two backends with identical semantics:

  * ``"host"``   — vectorized numpy (no compile step, fastest on CPU for
    one-off small evaluations);
  * ``"device"`` — the kernel layer: shape-bucketed jitted drivers over
    the Pallas kernels (`kernels/predicate`, `kernels/groupagg`,
    `kernels/moments`, `kernels/histogram`).  Off-TPU the drivers lower
    through the pure-jnp kernel oracles (XLA) instead of Pallas interpret
    mode, which is a correctness emulator, not a performance path.

Resolution order: explicit argument > ``REPRO_EVAL_BACKEND`` env var >
platform default ("device" on TPU, "host" elsewhere).

The device backend additionally takes a partition-axis device mesh
(``REPRO_MESH`` env var / ``--mesh`` launch switch, resolved by
`repro.distributed.dataplane`): sketch construction and query evaluation
shard the partition axis over the mesh with `shard_map`, one ingest/eval
pass per device over its local partitions.  Unset (or ``0``/``off``) means
the single-device path; a degenerate 1-device mesh is bit-identical to it.
"""
from __future__ import annotations

import dataclasses
import os
import warnings

import jax

BACKENDS = ("host", "device")


def default_backend() -> str:
    """The platform default: kernels on TPU, numpy elsewhere."""
    env = os.environ.get("REPRO_EVAL_BACKEND", "")
    if env:
        return resolve_backend(env)
    return "device" if jax.default_backend() == "tpu" else "host"


def resolve_backend(backend: str | None) -> str:
    if backend is None or backend == "":
        return default_backend()
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    return backend


def default_mesh_devices() -> int:
    """Partition-axis device count from ``REPRO_MESH``.

    ``""``/``"0"``/``"off"`` → 0 (no mesh: the single-device data plane);
    ``"auto"``/``"all"`` → every local device; an integer → that many.
    """
    env = os.environ.get("REPRO_MESH", "").strip().lower()
    if env in ("", "0", "off", "none"):
        return 0
    if env in ("auto", "all"):
        return len(jax.devices())
    n = int(env)
    if n < 1 or n > len(jax.devices()):
        raise ValueError(
            f"REPRO_MESH={n} but {len(jax.devices())} device(s) are available"
        )
    return n


def use_checkout_compile_cache(checkout) -> str:
    """Keep JAX's persistent compilation cache at a fixed place; → its dir.

    ``JAX_COMPILATION_CACHE_DIR``, when set, governs (JAX reads it itself)
    and no other directory is set.  Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — a fixed path, since the directory is part
    of what a later run must find again.  The kernels compile in well
    under a second, so the minimum compile time worth caching is zero.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = os.path.join(os.fspath(checkout), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def kernels_use_ref(use_ref: bool | None = None) -> bool:
    """Whether the device backend should run the jnp kernel oracles.

    On TPU the Pallas kernels run natively; elsewhere the oracles are the
    compiled (XLA) form of the same math — Pallas interpret mode stays
    available for parity tests via an explicit ``use_ref=False``.
    """
    if use_ref is None:
        return jax.default_backend() != "tpu"
    return use_ref


# --------------------------------------------------------------------------
# unified execution options (public API)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ExecOptions:
    """Execution policy for every offline-plane entry point, in one value.

    Consolidates the ``backend=`` / ``plane=`` / ``use_ref=`` keywords that
    used to be threaded separately through `build_sketches`,
    `build_statistics`, `per_partition_answers_batch`, `train_picker`,
    `BatchPicker`, ...  Pass one ``options=ExecOptions(...)`` instead; the
    old keywords keep working through deprecation shims.

    Fields:
      * ``backend`` — ``"host"`` | ``"device"`` | None (resolve the
        platform default, see `resolve_backend`);
      * ``mesh`` — the partition-axis device mesh: ``"auto"`` (the
        ``REPRO_MESH`` policy, the default), ``None``/``0``/``"off"``
        (single-device), an int device count, or a resolved
        `PartitionPlane` / `jax.sharding.Mesh`;
      * ``use_ref`` — device-backend kernel form: None = the platform
        policy (`kernels_use_ref`), True = jnp oracles, False = Pallas;
      * ``parity_relaxation`` — opt-in allclose-not-bitwise device fast
        paths.  Default False keeps the bit-parity contract: every device
        result is byte-identical to host numpy.  True lets the GBDT
        boosting update stay device-resident across trees (XLA contracts
        pred + lr·leaf into an FMA numpy cannot express, and histograms
        lower scatter-free through the blocked one-hot matmul) — results
        are allclose to the host fit, not bitwise equal.
      * ``faults`` — a `repro.faults.FaultPolicy` (or None, the default:
        fault-free).  When set, the fault-aware read paths
        (`planner.QueryPlanner` chunk reads, `AnswerStore` exact reads)
        run each partition read through a deterministic seeded injector
        with retry/backoff/hedging; irrecoverable reads degrade the
        answer (planner) or raise `errors.PartitionReadError` (exact
        paths).  See docs/robustness.md.

    Frozen: derive variants with `replace` (e.g.
    ``opts.replace(backend="host")``).
    """

    backend: str | None = None
    mesh: object = "auto"
    use_ref: bool | None = None
    parity_relaxation: bool = False
    faults: object = None  # repro.faults.FaultPolicy | None

    def __post_init__(self):
        if self.backend not in (None, ""):
            resolve_backend(self.backend)  # raises on unknown names

    def resolved_backend(self) -> str:
        """The concrete backend this policy selects (explicit > env > platform)."""
        return resolve_backend(self.backend)

    def plane(self):
        """The resolved `PartitionPlane` (or None for the single-device
        path).  ``"auto"`` defers to the ``REPRO_MESH`` policy at call
        time, so one ExecOptions value stays valid across env changes."""
        from repro.distributed import dataplane

        mesh = self.mesh
        if mesh == 0 or (isinstance(mesh, str) and mesh.lower() in ("off", "none", "0")):
            mesh = None
        return dataplane.resolve_plane(mesh)

    def kernels_ref(self) -> bool:
        """Resolved oracle-vs-Pallas choice for the device backend."""
        return kernels_use_ref(self.use_ref)

    def replace(self, **changes) -> "ExecOptions":
        return dataclasses.replace(self, **changes)


class _Unset:
    """Sentinel distinguishing 'kwarg omitted' from an explicit None."""

    __slots__ = ()

    def __repr__(self) -> str:  # pragma: no cover - repr only
        return "<unset>"

    def __bool__(self) -> bool:
        return False


UNSET = _Unset()


def exec_options(options: ExecOptions | None = None, *, where: str,
                 stacklevel: int = 3, **legacy) -> ExecOptions:
    """Shim core: merge deprecated per-call keywords into an `ExecOptions`.

    ``legacy`` holds the function's old keywords (``backend=``, ``plane=``,
    ``use_ref=``) with `UNSET` defaults; any that were actually passed are
    folded into the returned options (``plane`` maps to ``mesh``) with a
    `DeprecationWarning` naming the call site.  Passing both ``options=``
    and a legacy keyword is a contradiction and raises.
    """
    given = {k: v for k, v in legacy.items() if v is not UNSET}
    if given and options is not None:
        raise ValueError(
            f"{where}: pass options=ExecOptions(...) or the legacy "
            f"{sorted(given)} keyword(s), not both"
        )
    if not given:
        return options if options is not None else ExecOptions()
    warnings.warn(
        f"{where}: the {'/'.join(sorted(given))} keyword(s) are deprecated; "
        "pass options=repro.api.ExecOptions(...) instead",
        DeprecationWarning,
        stacklevel=stacklevel,
    )
    if "plane" in given:
        given["mesh"] = given.pop("plane")
    return ExecOptions(**given)
