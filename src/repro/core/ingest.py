"""Accelerated sketch construction — the TPU ingest pipeline.

`build_statistics` computes the numeric tensors behind every sketch
(measures, categorical counts, histogram bucket counts, discrete-numeric
heavy-hitter counts) with the Pallas kernel layer in a single pass per
column; it is the engine behind `core.sketches.build_sketches(table,
backend="device")` and is tested for parity against the host tensors.

Per-partition sketch construction is embarrassingly parallel, so under a
partition mesh (`distributed/dataplane.py`, ``REPRO_MESH``) the column is
zero-padded along P to a mesh multiple and sharded; each device runs the
*same* jitted core over its local partitions (one HBM→VMEM stream per
device) and only the small (P, k) result tensors are gathered.  The cores
are mesh-oblivious — they see local-shard shapes — so sharded tensors are
bit-identical to the single-device ones and the `TRACES` census does not
grow with mesh size.

The AKMV hash path is vector-friendly and runs as plain XLA (hash +
top_k); equi-depth edge *placement* requires a global sort which XLA
already lowers optimally, so only the counting passes use custom kernels
(DESIGN §3, hardware-adaptation notes).

**Streaming merge path.**  All of these statistics are *mergeable*:
moments combine by count-weighted sums (+ min/max), histogram and
bincount tensors add elementwise, and the AKMV sketch merges by k-min
union (`core/sketches.py`).  `delta_statistics` computes tensors for
only the partitions appended since a snapshot and `merge_statistics`
reassembles the full-table result bit-identically — the O(new
partitions) ingest that keeps per-partition statistics maintainable
under data growth (docs/architecture.md, "streaming ingest plane").
"""
from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from repro.backends import UNSET, ExecOptions, exec_options
from repro.data.table import NUMERIC, Table
from repro.distributed import dataplane
from repro.kernels import ops
from repro.kernels.telemetry import TraceRegistry

TRACES = TraceRegistry("ingest")

_ROW_SPEC = dataplane.partition_spec(2, 0)  # (P, k) tensors: shard axis 0


def _moments_core(x, *, use_ref):
    """(P, R) → (P, 10) kernel moments; P is whatever shard this sees."""
    TRACES.note("moments", *x.shape)
    return ops.moments_op(x, use_ref=use_ref)


def _hist_core(x, edges, *, use_ref):
    TRACES.note("hist", *x.shape, edges.shape[1])
    return ops.histogram_range_op(x, edges, use_ref=use_ref)


def _bincount_core(codes, *, card, use_ref):
    TRACES.note("bincount", *codes.shape, card)
    return ops.bincount_op(codes, card, use_ref=use_ref)


_moments_jit = jax.jit(_moments_core, static_argnames=("use_ref",))
_hist_jit = jax.jit(_hist_core, static_argnames=("use_ref",))
_bincount_jit = jax.jit(_bincount_core, static_argnames=("card", "use_ref"))
_JIT_OF = {_moments_core: _moments_jit, _hist_core: _hist_jit,
           _bincount_core: _bincount_jit}


def _partition_resident(plane, arr) -> jax.Array:
    """One host→device transfer per column: whole on the single device,
    zero-padded + sharded along P under a mesh.  Device arrays pass
    through, so a column feeding several cores (moments + histogram)
    ships exactly once."""
    if isinstance(arr, jax.Array):
        return arr
    return jnp.asarray(arr) if plane is None else plane.shard_partitions(arr)


def _per_partition(plane, core, arrays, num_partitions, **static) -> np.ndarray:
    """Run one counting core over every partition: directly on the single
    device, or sharded along P with the pad partitions sliced off."""
    arrays = [_partition_resident(plane, a) for a in arrays]
    if plane is None:
        return np.asarray(_JIT_OF[core](*arrays, **static))[:num_partitions]
    f = dataplane.sharded_call(
        plane, core,
        in_specs=(_ROW_SPEC,) * len(arrays), out_specs=_ROW_SPEC,
        static=tuple(static.items()),
    )
    return plane.gather(f(*arrays), num_partitions)


def measures_from_moments(raw: np.ndarray, rows: int, positive: bool) -> np.ndarray:
    """Map kernel moments (P, 10) → paper measure layout (P, 9).

    Layout (sketches.MEASURE_NAMES): mean, min, max, meansq, std,
    logmean, logmeansq, logmin, logmax.  The kernel's power sums are
    shifted (``raw[:, 2:4]`` = Σ(x−s), Σ(x−s)², ``raw[:, 8]`` = s), so
    mean and meansq are re-based and std is taken from the centered
    moments — in float64, without the cancellation of Σx²/n − mean².
    """
    p = raw.shape[0]
    out = np.zeros((p, 9), np.float64)
    mn, mx, s1, s2, lmn, lmx, ls, lss, shift = [
        raw[:, i].astype(np.float64) for i in range(9)
    ]
    m1 = s1 / rows  # mean of x − shift
    m2 = s2 / rows
    out[:, 0] = shift + m1
    out[:, 1] = mn
    out[:, 2] = mx
    out[:, 3] = shift * shift + 2.0 * shift * m1 + m2
    out[:, 4] = np.sqrt(np.maximum(m2 - m1 * m1, 0.0))
    if positive:
        out[:, 5] = ls / rows
        out[:, 6] = lss / rows
        out[:, 7] = lmn
        out[:, 8] = lmx
    return out


def int_span(data: np.ndarray) -> tuple[int, int] | None:
    """(lo, hi) inclusive integer span of an integer-valued numeric column,
    or None when any value is non-integral (no width cap — the raw
    mergeable form `merge_statistics` combines across appends)."""
    if data.size == 0:
        return None
    codes = data.astype(np.int64)
    if not np.all(data == codes):
        return None
    return int(codes.min()), int(codes.max())


MAX_DISCRETE_WIDTH = 4096


def partition_int_spans(data: np.ndarray) -> np.ndarray:
    """Per-partition integer spans of a (P, R) numeric column:
    ``(P, 3) int64`` rows ``[lo, hi, ok]`` where ``ok`` is 1 iff every
    value in that partition is integral.  This is `int_span` evaluated
    per partition — the mergeable form the lifecycle plane folds when
    compaction or rebalancing changes which partitions survive
    (`fold_partition_spans`)."""
    p = data.shape[0]
    out = np.zeros((p, 3), np.int64)
    if data.size == 0:
        return out
    codes = data.astype(np.int64)
    ok = np.all(data == codes, axis=1)
    out[:, 0] = np.where(ok, codes.min(axis=1), 0)
    out[:, 1] = np.where(ok, codes.max(axis=1), 0)
    out[:, 2] = ok.astype(np.int64)
    return out


def fold_partition_spans(
    spans: np.ndarray, max_width: int = MAX_DISCRETE_WIDTH
) -> tuple[int, int] | None:
    """Fold (P, 3) per-partition spans into the column-level
    `discrete_span` result — ``(lo, width)`` iff every partition is
    integral and the union span fits the width cap, else None.  Agrees
    with `discrete_span` over the concatenated rows by construction, so
    a gather of surviving partitions can requalify a column exactly as a
    cold pass over the survivors would."""
    if spans.shape[0] == 0 or not np.all(spans[:, 2] == 1):
        return None
    lo = int(spans[:, 0].min())
    hi = int(spans[:, 1].max())
    width = hi - lo + 1
    return (lo, width) if width <= max_width else None


def discrete_span(data: np.ndarray, max_width: int = MAX_DISCRETE_WIDTH) -> tuple[int, int] | None:
    """(lo, width) when a numeric column is integer-valued with a small
    range — the case where exact heavy-hitter counts apply — else None."""
    span = int_span(data)
    if span is None:
        return None
    lo, hi = span
    width = hi - lo + 1
    return (lo, width) if width <= max_width else None


def merge_discrete_span(
    old_span: tuple[int, int] | None,
    new_span: tuple[int, int] | None,
    max_width: int = MAX_DISCRETE_WIDTH,
) -> tuple[int, int] | None:
    """Union of two observed inclusive (lo, hi) integer spans, or None
    when either side is disqualified (non-integral values, or never
    qualified) or the union exceeds the width cap.

    The single implementation of the cold pass's qualification rule for
    merges — `merge_statistics` and `core.sketches.update_sketches` both
    route through it, so an append widening a span past the cap (or a
    non-integral value arriving) disqualifies the column exactly as a
    cold `discrete_span` over the grown column would.
    """
    if old_span is None or new_span is None:
        return None
    lo = min(old_span[0], new_span[0])
    hi = max(old_span[1], new_span[1])
    return (lo, hi) if hi - lo + 1 <= max_width else None


# --------------------------------------------------------------------------
# mergeable-statistic primitives (streaming ingest)
# --------------------------------------------------------------------------
# Raw kernel-moment layout (see `_moments_core` / `measures_from_moments`):
# [min, max, Σ(x−s), Σ(x−s)², logmin, logmax, logsum, logsumsq, s, rows].
# Extrema combine by min/max, the log sums and row counts add, and the
# shifted power sums re-center onto the first chunk's shift — so two
# row-chunks of the same partitions merge in O(P) regardless of chunk size.
#
# The row-chunk merge primitives (`merge_moments`, `merge_bincounts`, the
# AKMV trio in `core/sketches.py`) are the mergeable-summary foundation;
# the *live* append path is partition-granular (`delta_statistics` +
# `merge_statistics`, `update_sketches`) and only exercises the span
# realignment — the row-chunk forms are held correct by
# `tests/test_streaming_ingest.py` as the paper-level mergeability
# property and as oracles for any future sub-partition streaming.
_MOMENT_MERGE = ("min", "max", "shifted", "shifted", "min", "max", "add", "add",
                 "keep", "add")


def merge_moments(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Merge raw (P, 10) kernel moments of two row-chunks of the same
    partitions.  Exact for min/max and integer-valued sums; float sums are
    re-associated (chunk partials added instead of one long fold), so a
    merged result matches the one-shot kernel to f32 rounding, not
    bitwise.  ``b``'s shifted sums move onto ``a``'s shift in float64
    (Σ(x−sa) = Σ(x−sb) + n·δ, Σ(x−sa)² = Σ(x−sb)² + 2δ·Σ(x−sb) + n·δ²).
    The streaming *partition-append* path never calls this on
    overlapping partitions — appended partitions fold their rows in one
    pass, which is how the append plane stays bit-identical to a cold
    rebuild."""
    a = a.astype(np.float64)
    b = b.astype(np.float64)
    how = np.asarray(_MOMENT_MERGE)
    out = np.where(how == "min", np.minimum(a, b),
                   np.where(how == "max", np.maximum(a, b),
                            np.where(how == "keep", a, a + b)))
    delta = b[:, 8] - a[:, 8]
    nb = b[:, 9]
    out[:, 2] += nb * delta
    out[:, 3] += 2.0 * delta * b[:, 2] + nb * delta * delta
    return out


def merge_bincounts(
    a: np.ndarray, b: np.ndarray, lo_a: int = 0, lo_b: int = 0
) -> tuple[np.ndarray, int]:
    """Elementwise-add two (P, width) count tensors whose first bins sit at
    absolute values ``lo_a`` / ``lo_b``; returns (merged, lo_merged).

    Counts are exact integers (held in float64), so aligning into the
    union span and adding is bit-identical to counting the union directly
    — the property the discrete heavy-hitter merge in `merge_statistics`
    relies on when an append widens a column's observed span."""
    lo = min(lo_a, lo_b)
    hi = max(lo_a + a.shape[1], lo_b + b.shape[1])
    out = np.zeros((a.shape[0], hi - lo), np.float64)
    out[:, lo_a - lo : lo_a - lo + a.shape[1]] += a
    out[:, lo_b - lo : lo_b - lo + b.shape[1]] += b
    return out, lo


def _embed_counts(counts: np.ndarray, lo: int, new_lo: int, new_width: int) -> np.ndarray:
    """Zero-embed (P, w) counts at span ``lo`` into a wider span."""
    out = np.zeros((counts.shape[0], new_width), np.float64)
    off = lo - new_lo
    out[:, off : off + counts.shape[1]] = counts
    return out


def _pad_partitions(arr: np.ndarray, target: int) -> np.ndarray:
    pad = target - arr.shape[0]
    if pad <= 0:
        return arr
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, widths)


def build_statistics(
    table: Table,
    use_ref: bool = False,
    discrete_counts: bool = False,
    plane=UNSET,
    partitions: tuple[int, int] | None = None,
    *,
    options: ExecOptions | None = None,
) -> dict[str, dict]:
    """Kernel-computed per-column statistics tensors.

    Returns {column: {"measures": (P,9)} | {"counts": (P,card)}} plus
    numeric histogram counts under "hist_counts" given equi-depth edges.
    With ``discrete_counts=True``, integer-valued numeric columns with a
    small range additionally carry exact per-partition frequencies
    ("discrete_counts", "discrete_lo") — the heavy-hitter input that
    `build_sketches(backend="device")` consumes.

    ``plane`` selects the partition mesh ("auto" = the ``REPRO_MESH``
    policy): each counting pass then runs one launch per device over its
    local partitions, bit-identical to the single-device tensors.

    ``partitions`` restricts the pass to a half-open partition range — the
    streaming-ingest *delta* path (`delta_statistics`): only the named
    partitions are read, so an append costs O(new partitions), not O(P).
    Delta ranges are zero-padded up to a power-of-two partition bucket
    before the kernels run (pad rows are sliced off before anything reads
    them), so a stream of arbitrary append sizes keeps the `TRACES`
    compile census at the bucket count instead of one entry per size.
    Every per-partition tensor is computed exactly as the full pass would
    — same kernels, same per-partition fold order — which is what lets
    `merge_statistics` reassemble a bit-identical full-table result.
    """
    from repro.core.clustering import bucket_size

    options = exec_options(options, where="build_statistics", plane=plane)
    plane = options.plane()
    out: dict[str, dict] = {}
    lo_part, hi_part = partitions if partitions is not None else (0, table.num_partitions)
    p = hi_part - lo_part
    delta = partitions is not None
    # delta passes pad to a bucket so the census stays bounded; the full
    # pass keeps its exact-P shapes (unchanged cold-path behavior)
    pb = bucket_size(p, minimum=1) if delta else p
    rows = table.rows_per_partition
    for spec in table.schema:
        data = table.columns[spec.name][lo_part:hi_part]
        if spec.kind == NUMERIC:
            # ships once, feeds both counting cores
            x = _partition_resident(plane, _pad_partitions(data, pb))
            mom = _per_partition(plane, _moments_core, (x,), p, use_ref=use_ref)
            edges = np.quantile(
                data.astype(np.float64), np.linspace(0, 1, 11), axis=1
            ).T
            hist = _per_partition(
                plane, _hist_core,
                (x, _pad_partitions(edges.astype(np.float32), pb)), p,
                use_ref=use_ref,
            )
            out[spec.name] = {
                "measures": measures_from_moments(mom, rows, spec.positive),
                "hist_edges": edges,
                "hist_counts": hist,
            }
            if discrete_counts:
                span = discrete_span(data)
                if delta:
                    # raw integer span of the delta rows (None = a non-
                    # integral value arrived): merge_statistics needs it to
                    # decide whether the merged column still qualifies
                    out[spec.name]["discrete_range_span"] = int_span(data)
                if span is not None:
                    lo, width = span
                    codes = (data.astype(np.int64) - lo).astype(np.int32)
                    # delta passes bucket the bin count too: the observed
                    # span width varies with every delta's data, and an
                    # exact-width kernel would re-trace per append; the
                    # pad bins receive no codes and are sliced off
                    wb = bucket_size(width, minimum=1) if delta else width
                    counts = _per_partition(
                        plane, _bincount_core, (_pad_partitions(codes, pb),), p,
                        card=wb, use_ref=use_ref,
                    )[:, :width]
                    out[spec.name]["discrete_counts"] = counts.astype(np.float64)
                    out[spec.name]["discrete_lo"] = lo
        else:
            counts = _per_partition(
                plane, _bincount_core, (_pad_partitions(data, pb),), p,
                card=spec.cardinality, use_ref=use_ref,
            )
            out[spec.name] = {"counts": counts.astype(np.float64)}
    return out


def delta_statistics(
    table: Table,
    start: int,
    use_ref: bool = False,
    discrete_counts: bool = False,
    plane=UNSET,
    *,
    options: ExecOptions | None = None,
) -> dict[str, dict]:
    """Statistics tensors for only the partitions appended at/after
    ``start`` — the O(new partitions) half of the streaming ingest plane.
    Feed the result to `merge_statistics` together with the pre-append
    tensors to obtain the full-table statistics bit-identically."""
    options = exec_options(options, where="delta_statistics", plane=plane)
    return build_statistics(
        table, use_ref=use_ref, discrete_counts=discrete_counts,
        partitions=(start, table.num_partitions), options=options,
    )


def merge_statistics(
    old: dict[str, dict], delta: dict[str, dict]
) -> dict[str, dict]:
    """Merge pre-append statistics with a `delta_statistics` result.

    Per-partition tensors (measures, histogram edges/counts, categorical
    counts) concatenate along P — appended partitions never touch existing
    rows, so the merge is bit-identical to a cold `build_statistics` over
    the grown table.  Discrete heavy-hitter counts are the one *global*
    tensor: their span is the column's observed integer range, so an
    append can widen it (both sides are re-embedded into the union span —
    exact, see `merge_bincounts`), push its width past
    ``MAX_DISCRETE_WIDTH``, or break integrality entirely (the counts are
    dropped, exactly as the cold pass would decide).
    """
    out: dict[str, dict] = {}
    for col, old_t in old.items():
        new_t = delta[col]
        merged: dict = {}
        if "counts" in old_t:  # categorical: fixed cardinality, concat
            merged["counts"] = np.concatenate(
                [old_t["counts"], new_t["counts"]], axis=0
            )
            out[col] = merged
            continue
        merged["measures"] = np.concatenate(
            [old_t["measures"], new_t["measures"]], axis=0
        )
        merged["hist_edges"] = np.concatenate(
            [old_t["hist_edges"], new_t["hist_edges"]], axis=0
        )
        merged["hist_counts"] = np.concatenate(
            [old_t["hist_counts"], new_t["hist_counts"]], axis=0
        )
        if "discrete_range_span" in new_t or "discrete_counts" in old_t:
            dspan = new_t.get("discrete_range_span")
            old_counts = old_t.get("discrete_counts")
            delta_p = new_t["measures"].shape[0]
            if delta_p == 0:  # empty append: the old tensors stand
                if old_counts is not None:
                    merged["discrete_counts"] = old_counts
                    merged["discrete_lo"] = old_t["discrete_lo"]
            elif old_counts is not None:
                lo_old = old_t["discrete_lo"]
                span = merge_discrete_span(
                    (lo_old, lo_old + old_counts.shape[1] - 1), dspan
                )
                if span is not None:
                    # union span; realigning exact integer counts is exact
                    lo, hi = span
                    width = hi - lo + 1
                    merged["discrete_counts"] = np.concatenate(
                        [
                            _embed_counts(old_counts, lo_old, lo, width),
                            _embed_counts(
                                new_t["discrete_counts"], new_t["discrete_lo"],
                                lo, width,
                            ),
                        ],
                        axis=0,
                    )
                    merged["discrete_lo"] = lo
            # else: span broken or width blown — drop, like the cold pass
        out[col] = merged
    return out
