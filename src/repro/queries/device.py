"""Device (kernel-layer) execution of per-partition query answers.

Routes `per_partition_answers` through the `kernels/predicate` +
`kernels/groupagg` Pallas kernels behind a shape-bucketed jitted driver,
reusing PR 1's pad-and-bucket pattern (`core/clustering.py::bucket_size`)
so the jit cache is bounded by the shape-bucket census rather than the
number of distinct (num_clauses, radix, n_raw) combinations a workload
produces.

**Canonical interval form.**  Every clause the kernel evaluates is a
half-open test ``lo <= x < hi`` on the float32 image of the column.  The
bounds are chosen so the float32 row set matches the host comparison
*bit-exactly* (`_f32_interval`): a float64 constant is snapped to the
nearest float32 boundary on the correct side, numeric equality becomes
``[v, nextafter(v))``, and coded-categorical equality ``[v, v+1)``.
``in``-lists expand to one interval clause per value in the same OR-group
and ``!=`` to the two-interval complement, so the only remaining host
fallbacks are genuinely inexpressible rows: non-finite columns under
``!=`` (NaN ≠ v is True; no interval says so), ``+inf`` under equality,
non-integer constants against coded categoricals, and clause blowups past
``MAX_CANON_CLAUSES``.

**Fused launch.**  A canonicalized query runs predicate eval and group
aggregation as ONE kernel (`kernels/fused.py`, XLA oracle
`kernels/ref.py::fused_eval_ref`): the row mask is folded into the group
codes tile-by-tile and contracted as a blocked one-hot matmul, so neither
the (B, R) mask nor an all-rows one-hot tensor ever lands in HBM, and no
path depends on XLA's single-threaded scatter.  On CPU single-device
default (`use_ref is None`, no mesh) the same fused op lowers to a numpy
executor (`_host_lowered_answers`) — bincount over mask-selected rows —
which is bit-identical to `engine._host_answers` and faster than it, so
"device" wins on every backend; pass ``use_ref`` explicitly to pin the
jitted XLA-ref or Pallas lowering (tests, mesh runs do).

**Stacked batching.**  Queries sharing a shape signature
``(C_b, G_b, radix_b, V_b)`` are stacked along the partition axis —
Q queries × N partitions become one (Q·N, ...) kernel launch — and the
stack depth is itself bucketed to a power of two, so a whole training
workload compiles a handful of executables and then streams.  Padding is
masked, never observed: padded clause slots are always-false members of a
real OR-group, padded OR-groups get one always-true clause, padded group
buckets receive no codes, padded value rows are zero, and padded queries
are sliced off before unpacking.

**Mesh-oblivious drivers.**  The jitted cores (`_eval_core`,
`_eval_nopred_core`) take whatever (n_cols+1, P, R) stack they are handed
— the full table on the single-device path, one device's local shard
under a partition mesh (`distributed/dataplane.py`), where
`EvalCache.device_stack` is sharded along P and the same cores run inside
`shard_map` with the per-query descriptors replicated.  Per-partition
math is unchanged either way, so sharded answers are bit-identical to
single-device answers, and the census keys (local-shard shapes) keep one
executable per shape-bucket signature regardless of mesh size.

Trace-count telemetry (`TRACES`) mirrors `core/clustering.py`: the
compile-bound test asserts the census, `bench_offline` reports it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.clustering import bucket_size
from repro.data.table import CATEGORICAL, Table
from repro.distributed import dataplane
from repro.kernels import ops
from repro.kernels.telemetry import TraceRegistry
from repro.queries import engine
from repro.queries.ir import Aggregate, Predicate, Query

TRACES = TraceRegistry("query_eval")

# cap on stacked f32 elements per launch (Q_b · N · max(C_b, V_b) · R)
MAX_STACK_ELEMS = 1 << 25
MAX_STACK_QUERIES = 64

# in-list / != expansion stops here: a wider predicate would blow the
# clause shape bucket (and the census) for one query — host fallback
MAX_CANON_CLAUSES = 24

_F32_INF = np.float32(np.inf)
_F32_TINY = np.float32(np.finfo(np.float32).tiny)  # smallest normal


# --------------------------------------------------------------------------
# canonical interval form
# --------------------------------------------------------------------------
def _f32_interval(op: str, v: float) -> tuple[np.float32, np.float32] | None:
    """Float32 (lo, hi) with {x ∈ f32 : lo <= x < hi} == {x : x op v}.

    Exactness argument: numpy compares a float32 column against a Python
    float constant under weak scalar promotion — the constant is cast to
    float32 first — so the half-open interval only has to shift the
    boundary one ulp past ``vf = float32(v)`` on the inclusive side.
    """
    vf = np.float32(v)
    up = np.nextafter(vf, _F32_INF)
    if op == "<":
        return (-_F32_INF, vf)
    if op == "<=":
        return (-_F32_INF, up)
    if op == ">":
        return (up, _F32_INF)
    if op == ">=":
        return (vf, _F32_INF)
    if op == "==":
        return (vf, up)
    return None  # "!=", "in": complement / multi-interval — host fallback


@dataclasses.dataclass(frozen=True)
class CanonicalPredicate:
    """AND-of-OR-groups lowered to per-clause interval tests."""

    cols: tuple[str, ...]  # per-clause source column
    lo: np.ndarray  # (C,) float32 inclusive lower bounds
    hi: np.ndarray  # (C,) float32 exclusive upper bounds
    group_of: tuple[int, ...]  # per-clause OR-group index
    num_groups: int


def _is_code(v) -> bool:
    """True when v is an exact integer code value ([v, v+1) is sound)."""
    try:
        return float(v) == int(v)
    except (OverflowError, ValueError):
        return False


def _clause_intervals(
    table: Table, clause, cache: engine.EvalCache
) -> list[tuple[np.float32, np.float32]] | None:
    """Interval expansion of one clause (OR over the list), or None.

    Categorical ``in``/``!=`` expand per code value; numeric ``in``
    expands to per-value equality intervals and numeric ``!=`` to the
    two-sided complement — the latter only on all-finite columns, since
    the host's ``NaN != v`` is True and no interval pair can say so.
    """
    if table.spec(clause.col).kind == CATEGORICAL:
        if clause.op == "==" :
            return [(np.float32(clause.value), np.float32(clause.value + 1))]
        if clause.op == "in":
            if not all(_is_code(v) for v in clause.value):
                return None  # [v, v+1) would admit code ceil(v): host isin won't
            return [(np.float32(v), np.float32(v + 1)) for v in clause.value]
        if clause.op == "!=":
            if not _is_code(clause.value):
                return None
            v = int(clause.value)
            return [(-_F32_INF, np.float32(v)), (np.float32(v + 1), _F32_INF)]
        return None  # range ops on codes: host fallback
    if cache.has_posinf(clause.col):
        return None  # +inf breaks the half-open equality image
    if clause.op == "in":
        # host isin compares in float64 (the list is asarray'd, not a weak
        # scalar) — the f32 equality interval only matches when the value
        # IS its own float32 image, and never for non-finite values
        if not all(
            np.isfinite(np.float32(v)) and float(np.float32(v)) == float(v)
            for v in clause.value
        ):
            return None
        return [_f32_interval("==", float(v)) for v in clause.value]
    if clause.op == "!=":
        if cache.has_nonfinite(clause.col):
            return None  # host: NaN != v is True; intervals would say False
        vf = np.float32(clause.value)
        return [(-_F32_INF, vf), (np.nextafter(vf, _F32_INF), _F32_INF)]
    iv = _f32_interval(clause.op, float(clause.value))
    return None if iv is None else [iv]


def canonicalize_predicate(
    table: Table, predicate: Predicate, cache: engine.EvalCache | None = None
) -> CanonicalPredicate | None:
    """Interval form of the predicate, or None if it needs the host path."""
    cache = cache or engine.EvalCache(table)
    cols: list[str] = []
    lo: list[np.float32] = []
    hi: list[np.float32] = []
    group_of: list[int] = []
    for g, group in enumerate(predicate.groups):
        for clause in group.clauses:
            ivs = _clause_intervals(table, clause, cache)
            if ivs is None:
                return None
            # XLA CPU flushes subnormals to zero, so a nonzero-subnormal
            # boundary (e.g. nextafter(0) from ``<= 0.0``) would compare
            # as 0 inside the jitted lowerings — host fallback instead
            if any(
                b != 0 and np.isfinite(b) and abs(b) < _F32_TINY
                for iv in ivs for b in iv
            ):
                return None
            for ivl, ivh in ivs:
                cols.append(clause.col)
                lo.append(ivl)
                hi.append(ivh)
                group_of.append(g)
    if len(cols) > MAX_CANON_CLAUSES:
        return None
    return CanonicalPredicate(
        tuple(cols),
        np.asarray(lo, np.float32),
        np.asarray(hi, np.float32),
        tuple(group_of),
        len(predicate.groups),
    )


# --------------------------------------------------------------------------
# shape-bucket signatures
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Signature:
    """Static shapes of one driver launch (the jit cache key, minus Q_b)."""

    num_clauses: int  # C_b (0 = no-predicate driver)
    num_groups: int  # G_b
    radix: int  # radix_b
    n_raw: int  # V_b

    @property
    def has_predicate(self) -> bool:
        return self.num_clauses > 0


@dataclasses.dataclass
class _QueryPlan:
    query: Query
    canon: CanonicalPredicate
    radix: int
    n_raw: int
    plans: list
    sig: Signature


# coarse radix levels: fine power-of-two buckets fragment a workload into
# one-query signatures (measured: 26 sigs / 48 queries), defeating both the
# batching and the compile bound.  Radix only sizes the output block, so
# over-padding is cheap relative to the row pass.
_RADIX_LEVELS = (8, 128, 512, 2048)


def _radix_bucket(radix: int) -> int:
    for lvl in _RADIX_LEVELS:
        if radix <= lvl:
            return lvl
    return bucket_size(radix)  # generator caps radix at MAX_GROUPS = 4096


def _signature(canon: CanonicalPredicate, radix: int, n_raw: int) -> Signature:
    vb = max(4, bucket_size(n_raw, minimum=1))  # generator emits n_raw <= 4
    if len(canon.cols) == 0:
        return Signature(0, 0, _radix_bucket(radix), vb)
    gb = bucket_size(canon.num_groups, minimum=2)
    extra = gb - canon.num_groups  # padded OR-groups need an always-true clause each
    cb = bucket_size(len(canon.cols) + extra, minimum=4)
    return Signature(cb, gb, _radix_bucket(radix), vb)


def _stack_local(table: Table, plane=None) -> int:
    """Partition count of the stack each launch actually sees: the padded
    shape bucket (`engine.stack_partitions` — the streaming plane's
    append slack), divided over the mesh when sharded."""
    pb = engine.stack_partitions(table.num_partitions, plane)
    return pb // plane.num_devices if plane is not None else pb


def _max_stack(table: Table, sig: Signature, plane=None) -> int:
    """Largest power-of-two query stack that fits the element budget
    (clause gather and segment-sum output are the two bulk tensors).
    Under a partition mesh the budget is per *device*, so the local
    partition count is what multiplies in — deeper stacks fit as the
    mesh grows."""
    n_local = _stack_local(table, plane)
    per_query = n_local * (
        table.rows_per_partition * max(sig.num_clauses, sig.n_raw, 1)
        + sig.radix * sig.n_raw
    )
    q = MAX_STACK_QUERIES
    while q > 1 and q * per_query > MAX_STACK_ELEMS:
        q //= 2
    return q


def _chunks(items: list, size: int):
    for i in range(0, len(items), size):
        yield items[i : i + size]


# --------------------------------------------------------------------------
# jitted drivers (trace-counted)
# --------------------------------------------------------------------------
def _device_inputs(stack, col_idx, coefs, mults):
    """Gather clause columns and derive values/codes from the table stack.

    Everything per-query is a small descriptor; the (n_cols+1, P, R)
    stack is the only bulk tensor and it is already device-resident.
    """
    ncols1, p, r = stack.shape
    qb, cb = col_idx.shape
    vb = coefs.shape[1]
    # the einsums contract zero coefficients against EVERY column, and
    # 0·inf = NaN — sanitize the contraction image (queries whose own
    # aggregates touch a non-finite column fall back to the host path, so
    # zeroing here only silences unreferenced columns); clause gathers
    # below read the raw stack, where non-finite rows compare exactly
    flat = stack.reshape(ncols1, p * r)
    flat = jnp.where(jnp.isfinite(flat), flat, jnp.float32(0))
    # aggregate components: linear projections = coefficient matmul (MXU).
    # HIGHEST: the TPU's default f32 matmul rounds operands to bf16, which
    # loses sums past rtol 1e-5 and integer codes past 256
    hi = jax.lax.Precision.HIGHEST
    values = jnp.einsum("qvc,cs->qvs", coefs, flat, precision=hi)
    values = values.reshape(qb, vb, p, r).transpose(0, 2, 1, 3).reshape(qb * p, vb, r)
    # mixed-radix group codes: integer-valued f32 matvec (exact below 2^24)
    codes = jnp.einsum("qc,cs->qs", mults, flat, precision=hi).reshape(qb, p, r)
    codes = jnp.round(codes).astype(jnp.int32).reshape(qb * p, r)
    # clause columns: device gather instead of host stacking
    x = stack[col_idx]  # (Qb, Cb, P, R)
    x = x.transpose(0, 2, 1, 3).reshape(qb * p, cb, r)
    return x, values, codes


def _eval_core(stack, col_idx, lo, hi, gmap, coefs, mults, *, num_groups, radix, use_ref):
    """Mesh-oblivious driver body → (Q_b, P, V_b, radix) raw sums.

    `stack` is whatever shard this program sees: the whole table on the
    single-device path, one device's local partitions under `shard_map` —
    the body never knows which, so the census key (local shapes) is the
    same discipline either way.
    """
    qb, cb = col_idx.shape
    p = stack.shape[1]
    TRACES.note("eval", qb * p, cb, num_groups, radix, coefs.shape[1])
    x, values, codes = _device_inputs(stack, col_idx, coefs, mults)
    lo_b = jnp.repeat(lo, p, axis=0)  # (Qb*P, Cb)
    hi_b = jnp.repeat(hi, p, axis=0)
    gmap_b = jnp.repeat(gmap, p, axis=0)  # (Qb*P, Cb, Gb)
    # one launch: predicate mask folded into the blocked one-hot contraction
    out = ops.fused_eval_op(
        x, lo_b, hi_b, gmap_b, values, codes, radix, use_ref=use_ref
    )
    return out.reshape(qb, p, out.shape[1], out.shape[2])


def _eval_nopred_core(stack, coefs, mults, *, radix, use_ref):
    qb = coefs.shape[0]
    p = stack.shape[1]
    TRACES.note("eval_nopred", qb * p, radix, coefs.shape[1])
    _, values, codes = _device_inputs(
        stack, jnp.zeros((qb, 1), jnp.int32), coefs, mults
    )
    mask = jnp.ones((values.shape[0], values.shape[2]), jnp.float32)
    out = ops.group_aggregate_op(values, mask, codes, radix, use_ref=use_ref)
    return out.reshape(qb, p, out.shape[1], out.shape[2])


_eval_stacked = jax.jit(_eval_core, static_argnames=("num_groups", "radix", "use_ref"))
_eval_stacked_nopred = jax.jit(_eval_nopred_core, static_argnames=("radix", "use_ref"))

# shard_map specs for the sharded launch: the stack is partitioned along
# P, every per-query descriptor is replicated, answers come back P-major
_STACK_SPEC = dataplane.partition_spec(3, 1)
_OUT_SPEC = dataplane.partition_spec(4, 1)


# --------------------------------------------------------------------------
# per-query descriptors (small host arrays; the stack stays on device)
# --------------------------------------------------------------------------
def _descriptor(plan: _QueryPlan, cache: engine.EvalCache):
    """(col_idx (C_b,), lo, hi, gmap (C_b,G_b), coefs (V_b,n_cols+1),
    mults (n_cols+1,)) — everything the driver needs besides the stack."""
    sig, canon, table = plan.sig, plan.canon, cache.table
    cb, gb, vb = sig.num_clauses, sig.num_groups, sig.n_raw
    c, g = len(canon.cols), canon.num_groups
    ncols1 = cache.ones_index + 1

    col_idx = np.zeros(max(cb, 1), np.int32)
    lo = np.full(max(cb, 1), np.float32(1.0), np.float32)  # always-false slot
    hi = np.full(max(cb, 1), np.float32(-1.0), np.float32)
    gmap = np.zeros((max(cb, 1), max(gb, 1)), np.float32)
    for j, col in enumerate(canon.cols):
        col_idx[j] = cache.col_index[col]
        lo[j] = canon.lo[j]
        hi[j] = canon.hi[j]
        gmap[j, canon.group_of[j]] = 1.0
    # padded OR-groups: one always-true clause each (ones column ∈ [0.5, 1.5))
    for k in range(gb - g):
        col_idx[c + k] = cache.ones_index
        lo[c + k] = np.float32(0.5)
        hi[c + k] = np.float32(1.5)
        gmap[c + k, g + k] = 1.0
    # remaining padded clause slots stay always-false, parked in group 0
    gmap[c + (gb - g) :, 0] = 1.0

    coefs = np.zeros((vb, ncols1), np.float32)
    coefs[0, cache.ones_index] = 1.0  # raw component 0 = passing-row count
    k = 1
    for agg in plan.query.aggregates:
        if agg.kind == "count":
            continue
        for coef, col in agg.terms:
            coefs[k, cache.col_index[col]] += np.float32(coef)
        k += 1

    mults = np.zeros(ncols1, np.float32)
    mult = 1
    for name in reversed(plan.query.groupby):
        mults[cache.col_index[name]] = np.float32(mult)
        mult *= table.spec(name).cardinality
    return col_idx, lo, hi, gmap, coefs, mults


def _run_chunk(
    chunk: list[_QueryPlan], cache: engine.EvalCache, use_ref: bool
) -> list[engine.PartitionAnswers]:
    sig = chunk[0].sig
    table = cache.table
    n = table.num_partitions
    qb = bucket_size(len(chunk), minimum=1)
    ncols1 = cache.ones_index + 1
    stack = cache.device_stack()

    with TraceAnnotation("eval.prepare"):
        col_idx = np.zeros((qb, max(sig.num_clauses, 1)), np.int32)
        lo = np.full((qb, max(sig.num_clauses, 1)), np.float32(1.0), np.float32)
        hi = np.full((qb, max(sig.num_clauses, 1)), np.float32(-1.0), np.float32)
        gmap = np.zeros(
            (qb, max(sig.num_clauses, 1), max(sig.num_groups, 1)), np.float32
        )
        coefs = np.zeros((qb, sig.n_raw, ncols1), np.float32)
        mults = np.zeros((qb, ncols1), np.float32)
        for i, plan in enumerate(chunk):
            col_idx[i], lo[i], hi[i], gmap[i], coefs[i], mults[i] = _descriptor(plan, cache)

    plane = cache.plane
    # the launch ships its descriptors host → device, to every device of a mesh
    sent = (coefs, mults) + ((col_idx, lo, hi, gmap) if sig.has_predicate else ())
    cache.upload_bytes += sum(a.nbytes for a in sent) * (
        1 if plane is None else plane.num_devices
    )
    if plane is None:
        if sig.has_predicate:
            out = _eval_stacked(
                stack, col_idx, lo, hi, gmap, coefs, mults,
                num_groups=sig.num_groups, radix=sig.radix, use_ref=use_ref,
            )
        else:
            out = _eval_stacked_nopred(
                stack, coefs, mults, radix=sig.radix, use_ref=use_ref
            )
    elif sig.has_predicate:
        f = dataplane.sharded_call(
            plane, _eval_core,
            in_specs=(_STACK_SPEC,) + (dataplane.REPLICATED,) * 6,
            out_specs=_OUT_SPEC,
            static=(("num_groups", sig.num_groups), ("radix", sig.radix),
                    ("use_ref", use_ref)),
        )
        out = f(stack, col_idx, lo, hi, gmap, coefs, mults)
    else:
        f = dataplane.sharded_call(
            plane, _eval_nopred_core,
            in_specs=(_STACK_SPEC, dataplane.REPLICATED, dataplane.REPLICATED),
            out_specs=_OUT_SPEC,
            static=(("radix", sig.radix), ("use_ref", use_ref)),
        )
        out = f(stack, coefs, mults)

    # [:, :n] slices off the mesh's zero pad partitions (no-op unsharded)
    out = np.asarray(out, np.float64)[:, :n]
    answers = []
    with TraceAnnotation("eval.unpack"):
        for i, plan in enumerate(chunk):
            raw = out[i, :, : plan.n_raw, : plan.radix].transpose(0, 2, 1)
            answers.append(engine._answers_from_raw(plan.query, raw, plan.plans))
    return answers


# --------------------------------------------------------------------------
# numpy lowering of the fused op (single-device CPU default)
# --------------------------------------------------------------------------
def _host_lowered_answers(
    plan: _QueryPlan, cache: engine.EvalCache
) -> engine.PartitionAnswers:
    """CPU lowering of the fused predicate+aggregate op.

    Same canonical intervals, same fold-mask-into-codes structure as the
    kernels — expressed as mask-selected `np.bincount` segment sums, which
    multi-issue on CPU where XLA's scatter serializes.  Bit-identical to
    `engine._host_answers` (integer counts are exact in any order; sums
    accumulate in float64 over the same selected rows in the same row-major
    order), and ~2× faster: counts ride an unweighted integer bincount and
    only occupied groups are materialized.
    """
    canon, q = plan.canon, plan.query
    n = cache.table.num_partitions
    if len(canon.cols) == 0:
        sel = None
    else:
        m: np.ndarray | None = None
        per_group: dict[int, list[int]] = {}
        for j, g in enumerate(canon.group_of):
            per_group.setdefault(g, []).append(j)
        for idxs in per_group.values():
            gmask: np.ndarray | None = None
            for j in idxs:
                x = cache.f32(canon.cols[j])
                cm = (x >= canon.lo[j]) & (x < canon.hi[j])
                gmask = cm if gmask is None else np.logical_or(gmask, cm, out=gmask)
            m = gmask if m is None else np.logical_and(m, gmask, out=m)
        sel = np.flatnonzero(m.ravel())
    seg, radix = cache.segments(q.groupby)
    segm = seg if sel is None else seg[sel]
    cnt = np.bincount(segm, minlength=n * radix).reshape(n, radix)
    occupied = np.flatnonzero(cnt.sum(axis=0))
    raw = np.zeros((n, occupied.size, plan.n_raw), np.float64)
    raw[:, :, 0] = cnt[:, occupied]
    k = 1
    for agg in q.aggregates:
        if agg.kind == "count":
            continue
        w = cache.projection(agg).reshape(-1)
        s = np.bincount(
            segm, weights=w if sel is None else w[sel], minlength=n * radix
        )
        raw[:, :, k] = s.reshape(n, radix)[:, occupied]
        k += 1
    return engine.PartitionAnswers(q, occupied, raw, plan.plans)


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------
def _plan_workload(table: Table, queries: list[Query], cache: engine.EvalCache):
    """→ ({signature: [(index, plan)]}, [(index, query)] host fallbacks)."""
    grouped: dict[Signature, list[tuple[int, _QueryPlan]]] = {}
    fallback: list[tuple[int, Query]] = []
    for i, q in enumerate(queries):
        canon = canonicalize_predicate(table, q.predicate, cache)
        if canon is None or any(
            cache.has_nonfinite(col) for agg in q.aggregates for _, col in agg.terms
        ):
            fallback.append((i, q))
            continue
        radix = engine.group_radix_checked(table, q.groupby)
        plans, n_raw = engine.plan_aggregates(q.aggregates)
        sig = _signature(canon, radix, n_raw)
        grouped.setdefault(sig, []).append(
            (i, _QueryPlan(q, canon, radix, n_raw, plans, sig))
        )
    return grouped, fallback


def eval_workload(
    table: Table,
    queries: list[Query],
    cache: engine.EvalCache | None = None,
    use_ref: bool | None = None,
) -> list[engine.PartitionAnswers]:
    """Kernel-backed A_{g,i} for a workload; order matches the input.

    Lowering choice: ``use_ref`` pins the jitted XLA ref (True) or the
    Pallas kernel (False).  Left as None off-TPU with no mesh, the fused
    op lowers to the numpy executor instead — bit-identical to both and
    the fastest CPU path (nothing to trace, so the census bound holds
    trivially).  A mesh or a TPU always takes the jitted route.
    """
    from repro.backends import kernels_use_ref

    cache = cache or engine.EvalCache(table)
    with TraceAnnotation("eval.prepare"):
        grouped, fallback = _plan_workload(table, queries, cache)
    out: list[engine.PartitionAnswers | None] = [None] * len(queries)
    for i, q in fallback:  # inexpressible predicates: exact-parity host path
        out[i] = engine._host_answers(table, q, cache)
    if use_ref is None and cache.plane is None and jax.default_backend() != "tpu":
        for _sig, entries in grouped.items():
            for i, plan in entries:
                out[i] = _host_lowered_answers(plan, cache)
        return out
    use_ref = kernels_use_ref(use_ref)
    for sig, entries in grouped.items():
        for chunk in _chunks(entries, _max_stack(table, sig, cache.plane)):
            answers = _run_chunk([p for _, p in chunk], cache, use_ref)
            for (i, _), ans in zip(chunk, answers):
                out[i] = ans
    return out


def predicate_mask_device(
    table: Table,
    predicate: Predicate,
    cache: engine.EvalCache | None = None,
    use_ref: bool | None = None,
) -> np.ndarray | None:
    """Kernel row mask (N, R) bool, or None if the predicate needs the host
    path — the bit-parity surface the edge-case sweep tests directly."""
    from repro.backends import kernels_use_ref

    cache = cache or engine.EvalCache(table)
    canon = canonicalize_predicate(table, predicate, cache)
    if canon is None:
        return None
    n, r = table.num_partitions, table.rows_per_partition
    if len(canon.cols) == 0:
        return np.ones((n, r), bool)
    plans, n_raw = engine.plan_aggregates((Aggregate("count"),))
    sig = _signature(canon, 1, n_raw)
    plan = _QueryPlan(Query((Aggregate("count"),), predicate), canon, 1, n_raw, plans, sig)
    col_idx, lo, hi, gmap, _, _ = _descriptor(plan, cache)
    names = [s.name for s in table.schema]
    cols = np.stack(
        [
            cache.f32(names[i]) if i < cache.ones_index
            else np.ones((n, r), np.float32)
            for i in col_idx
        ],
        axis=1,
    )  # (N, C_b, R), gathered host-side — no device round-trip
    mask, _ = ops.predicate_eval_op(
        jnp.asarray(cols),
        jnp.asarray(np.broadcast_to(lo, (n, lo.shape[0]))),
        jnp.asarray(np.broadcast_to(hi, (n, hi.shape[0]))),
        jnp.asarray(gmap),
        sig.num_groups,
        use_ref=kernels_use_ref(use_ref),
    )
    return np.asarray(mask) > 0.5


def workload_census(
    table: Table, queries: list[Query], cache: engine.EvalCache | None = None
) -> set[tuple]:
    """Expected trace keys for a workload — the compile-count upper bound.

    Mirrors `eval_workload`'s grouping exactly, so
    ``TRACES.total() <= len(workload_census(...))`` is the acceptance
    assertion for bounded compiles.
    """
    cache = cache or engine.EvalCache(table)
    grouped, _ = _plan_workload(table, queries, cache)
    # census keys use the shapes each launch *sees*: the bucket-padded
    # stack (local shard under a mesh) — independent of mesh size, and
    # flat across in-bucket streaming appends
    n_local = _stack_local(table, cache.plane)
    keys: set[tuple] = set()
    for sig, entries in grouped.items():
        for chunk in _chunks(entries, _max_stack(table, sig, cache.plane)):
            b = bucket_size(len(chunk), minimum=1) * n_local
            if sig.has_predicate:
                keys.add(
                    ("eval", b, sig.num_clauses, sig.num_groups, sig.radix, sig.n_raw)
                )
            else:
                keys.add(("eval_nopred", b, sig.radix, sig.n_raw))
    return keys
