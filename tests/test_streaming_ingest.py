"""Streaming partition ingest: append-equivalence, merges, invalidation.

The contract under test (ISSUE 5 tentpole): appending partitions through
`append_partitions` / `concat_tables(into=)` updates every derived
structure *incrementally* — sketch rows for only the new partitions
(`update_sketches`/`SketchStore`), an in-place device-stack slack write
(`EvalCache`), a delta-only answer merge (`AnswerStore`) — and each of
them is **bit-identical** to a cold full rebuild of the grown table, on
the single-device path and on 1/2/8-device partition meshes, including
appends that overflow the stack's P shape bucket.  The compile census
stays flat across in-bucket appends.  CI runs this file in the forced
8-device lane alongside ``test_distributed_dataplane.py``.
"""
import jax
import numpy as np
import pytest

from repro.core import ingest
from repro.core.sketches import (
    SketchStore,
    _akmv,
    akmv_finalize,
    akmv_state,
    build_sketches,
    merge_akmv_states,
    update_sketches,
)
from repro.data.datasets import make_dataset
from repro.data.table import CATEGORICAL, NUMERIC, ColumnSpec, Table, append_partitions, concat_tables
from repro.kernels import ops
from repro.queries import device
from repro.queries.engine import (
    AnswerStore,
    EvalCache,
    per_partition_answers_batch,
    stack_partitions,
)
from repro.queries.generator import WorkloadSpec

PLANES = (None, 2, 8)  # single-device path + real meshes


def _plane_or_skip(plane):
    if plane is not None and plane > len(jax.devices()):
        pytest.skip(f"needs {plane} devices, have {len(jax.devices())} "
                    "(CI sets XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return plane


def _delta(parts, rows=64, seed=7):
    t = make_dataset("kdd", num_partitions=max(parts, 1),
                     rows_per_partition=rows, layout="random", seed=seed)
    if parts == 0:  # empty append: a 0-partition column mapping
        return {k: v[:0] for k, v in t.columns.items()}
    return t


def assert_sketches_equal(a, b):
    assert a.num_partitions == b.num_partitions
    for name, ca in a.columns.items():
        cb = b.columns[name]
        for field in ("measures", "hist_edges", "cat_counts", "ndv",
                      "dv_freq", "hh_stats", "global_hh", "bitmap"):
            x, y = getattr(ca, field), getattr(cb, field)
            assert (x is None) == (y is None), (name, field)
            if x is not None:
                assert np.array_equal(x, y), (name, field)
        assert ca.hh_items == cb.hh_items, name
        assert ca.discrete_span == cb.discrete_span, name


def assert_answers_equal(got, want):
    for g, w in zip(got, want):
        assert np.array_equal(g.group_keys, w.group_keys)
        assert np.array_equal(g.raw, w.raw)


# --------------------------------------------------------------------------
# the tentpole sweep: k successive appends ≡ cold rebuild, on every mesh
# --------------------------------------------------------------------------
@pytest.mark.parametrize("plane", PLANES, ids=["single", "mesh2", "mesh8"])
@pytest.mark.parametrize("backend", ["host", "device"])
def test_append_equivalence_sweep(plane, backend):
    """Base P=5 (bucket 8), then: in-bucket append (+3 → 8), empty append,
    bucket-overflow append (+9 → 17, bucket 32).  After every step the
    incrementally maintained sketches and answers equal a cold rebuild
    bitwise."""
    _plane_or_skip(plane)
    if backend == "host" and plane is not None:
        pytest.skip("the host backend has no mesh axis")
    table = make_dataset("kdd", num_partitions=5, rows_per_partition=64)
    queries = WorkloadSpec(table, seed=3).sample_workload(8)
    sketch_store = SketchStore(table, backend=backend, plane=plane)
    answer_store = AnswerStore(table, backend=backend, plane=plane)
    answer_store.get_batch(queries)  # warm the LRU pre-append

    steps = [_delta(3, seed=11), _delta(0, seed=12), _delta(9, seed=13)]
    for i, delta in enumerate(steps):
        append_partitions(table, delta)
        sk = sketch_store.sketches()
        cold_sk = build_sketches(table, backend=backend, plane=plane)
        assert_sketches_equal(sk, cold_sk)
        got = answer_store.get_batch(queries)
        cold = per_partition_answers_batch(
            table, queries, backend=backend, cache=EvalCache(table, plane=plane)
        )
        assert_answers_equal(got, cold)
        assert all(a.raw.shape[0] == table.num_partitions for a in got)
    assert sketch_store.incremental_updates == len(steps)
    assert sketch_store.full_rebuilds == 0
    # every pre-append entry survived all three appends (none were dropped)
    assert answer_store.carried >= len(queries)


def test_single_row_partitions():
    """rows_per_partition=1 — the degenerate partition geometry."""
    schema = (
        ColumnSpec("v", NUMERIC),
        ColumnSpec("c", CATEGORICAL, cardinality=3, groupable=True),
    )

    def mk(parts, seed):
        r = np.random.default_rng(seed)
        return Table(schema, {
            "v": r.normal(size=(parts, 1)).astype(np.float32),
            "c": r.integers(0, 3, size=(parts, 1)).astype(np.int32),
        }, name="tiny")

    table = mk(4, 1)
    store = SketchStore(table, backend="host")
    append_partitions(table, mk(3, 2))
    assert_sketches_equal(store.sketches(), build_sketches(table, backend="host"))


def test_census_flat_for_in_bucket_appends():
    """An in-bucket append changes no stack shape, so re-evaluating the
    workload compiles nothing new — the streaming plane's compile-cost
    contract."""
    table = make_dataset("kdd", num_partitions=6, rows_per_partition=64)
    queries = WorkloadSpec(table, seed=5).sample_workload(8)
    cache = EvalCache(table, plane=None)
    assert stack_partitions(6) == 8
    # use_ref=True pins the jitted lowering: the compile-cost contract is
    # about the jit cache (the CPU-default numpy route traces nothing)
    device.eval_workload(table, queries, cache=cache, use_ref=True)
    device.TRACES.reset()
    append_partitions(table, _delta(2, seed=21))  # 6 → 8: still in bucket 8
    device.eval_workload(table, queries, cache=cache, use_ref=True)
    assert device.TRACES.total() == 0, device.TRACES.counts()
    assert cache.stack_appends == 1 and cache.device_stack().shape[1] == 8
    # census bookkeeping agrees with the driver across the append
    census = device.workload_census(table, queries, cache)
    device.eval_workload(table, queries, cache=cache, use_ref=True)
    assert device.TRACES.total() <= len(census)


def test_bucket_overflow_rebuilds_and_stays_exact():
    table = make_dataset("kdd", num_partitions=6, rows_per_partition=64)
    queries = WorkloadSpec(table, seed=5).sample_workload(6)
    cache = EvalCache(table, plane=None)
    device.eval_workload(table, queries, cache=cache)
    rebuilds0 = cache.stack_rebuilds
    append_partitions(table, _delta(4, seed=22))  # 6 → 10: overflows bucket 8
    got = device.eval_workload(table, queries, cache=cache)
    assert cache.device_stack().shape[1] == 16
    assert cache.stack_rebuilds == rebuilds0 + 1 and cache.stack_appends == 0
    cold = device.eval_workload(table, queries, cache=EvalCache(table, plane=None))
    assert_answers_equal(got, cold)


# --------------------------------------------------------------------------
# mergeable-statistic primitives
# --------------------------------------------------------------------------
def test_merge_moments_row_chunks():
    rng = np.random.default_rng(1)
    x = np.abs(rng.normal(size=(5, 200))).astype(np.float32) + 0.1
    full = np.asarray(ops.moments_op(x))
    merged = ingest.merge_moments(
        np.asarray(ops.moments_op(x[:, :80])),
        np.asarray(ops.moments_op(x[:, 80:])),
    )
    # extrema are exact; sums are re-associated → f32-close, not bitwise
    for i, how in enumerate(ingest._MOMENT_MERGE):
        if how in ("min", "max"):
            np.testing.assert_array_equal(merged[:, i], full[:, i])
    np.testing.assert_allclose(
        ingest.measures_from_moments(merged, 200, positive=True),
        ingest.measures_from_moments(full, 200, positive=True),
        rtol=1e-4, atol=1e-4,
    )


def test_merge_bincounts_realigns_spans_exactly():
    rng = np.random.default_rng(2)
    a_vals = rng.integers(3, 10, size=(4, 100))
    b_vals = rng.integers(-5, 4, size=(4, 60))
    from repro.core.sketches import _partition_bincount

    a = _partition_bincount(a_vals - 3, 7)
    b = _partition_bincount(b_vals + 5, 9)
    merged, lo = ingest.merge_bincounts(a, b, lo_a=3, lo_b=-5)
    assert lo == -5
    both = np.concatenate([a_vals, b_vals], axis=1)
    want = _partition_bincount(both + 5, merged.shape[1])
    np.testing.assert_array_equal(merged, want)


def test_akmv_merge_bit_identical():
    rng = np.random.default_rng(3)
    cases = [
        rng.normal(size=(5, 300)).astype(np.float32),  # d > k on each side
        rng.integers(0, 9, size=(4, 257)).astype(np.int32),  # few distinct
        np.full((3, 130), 7.25, np.float32),  # constant
        rng.integers(0, 2, size=(2, 64)).astype(np.int32),  # r < k
    ]
    for col in cases:
        cut = col.shape[1] // 3
        merged = merge_akmv_states(akmv_state(col[:, :cut]), akmv_state(col[:, cut:]))
        ndv, freq = akmv_finalize(merged)
        ndv0, freq0 = _akmv(col)
        np.testing.assert_array_equal(ndv, ndv0)
        np.testing.assert_array_equal(freq, freq0)


def test_merge_statistics_matches_cold_build():
    table = make_dataset("kdd", num_partitions=6, rows_per_partition=64)
    old = ingest.build_statistics(table, discrete_counts=True, plane=None)
    start = table.num_partitions
    append_partitions(table, _delta(4, seed=31))
    merged = ingest.merge_statistics(
        old, ingest.delta_statistics(table, start, discrete_counts=True, plane=None)
    )
    cold = ingest.build_statistics(table, discrete_counts=True, plane=None)
    for col in cold:
        assert set(cold[col]) == set(merged[col]), col
        for key in cold[col]:
            assert np.array_equal(
                np.asarray(merged[col][key]), np.asarray(cold[col][key])
            ), (col, key)


def test_append_disqualifies_discrete_heavy_hitters():
    """A delta with a non-integral value breaks the discrete-numeric HH
    qualification for the whole column — the incremental update must zero
    the *old* partitions' HH rows exactly as a cold rebuild decides."""
    schema = (ColumnSpec("d", NUMERIC),)

    def mk(parts, fill):
        return Table(schema, {"d": np.full((parts, 32), fill, np.float32)},
                     name="disq")

    table = mk(4, 3.0)
    sk0 = build_sketches(table, backend="host")
    assert sk0.columns["d"].discrete_span == (3, 3)
    assert sk0.columns["d"].hh_stats[:, 0].min() == 1.0
    append_partitions(table, mk(2, 0.5))  # non-integral value arrives
    got = update_sketches(sk0, table, 4, backend="host")
    cold = build_sketches(table, backend="host")
    assert_sketches_equal(got, cold)
    assert got.columns["d"].discrete_span is None
    assert np.all(got.columns["d"].hh_stats == 0)


# --------------------------------------------------------------------------
# invalidation semantics
# --------------------------------------------------------------------------
def test_append_log_and_append_range():
    table = make_dataset("kdd", num_partitions=4, rows_per_partition=64)
    assert table.append_range(0) == (4, 4)
    append_partitions(table, _delta(2, seed=41))
    append_partitions(table, _delta(3, seed=42))
    assert table.version == 2 and table.append_log == {1: 4, 2: 6}
    assert table.append_range(0) == (4, 9)
    assert table.append_range(1) == (6, 9)
    assert table.append_range(2) == (9, 9)
    table.version += 1  # an unlogged (non-append) mutation breaks the chain
    assert table.append_range(0) is None
    assert table.append_range(3) == (9, 9)


def test_mutation_hidden_behind_append_raises():
    """An out-of-band corner mutation followed by a legitimate append must
    NOT slip through the append fast path: the pre-append region is
    re-fingerprinted before anything is carried across."""
    table = make_dataset("kdd", num_partitions=4, rows_per_partition=64)
    queries = WorkloadSpec(table, seed=2).sample_workload(4)
    store = AnswerStore(table, backend="host")
    store.get_batch(queries)
    col = table.numeric_columns[0]
    table.columns[col][0, 0] += 5.0  # silent mutation...
    append_partitions(table, _delta(2, seed=45))  # ...hidden by an append
    with pytest.raises(RuntimeError, match="pre-append partitions changed"):
        store.get_batch(queries)


def test_append_log_is_bounded():
    table = make_dataset("kdd", num_partitions=2, rows_per_partition=16)
    empty = {k: v[:0] for k, v in table.columns.items()}
    for _ in range(Table.MAX_APPEND_LOG + 10):
        append_partitions(table, empty)
    assert len(table.append_log) == Table.MAX_APPEND_LOG
    # recent snapshots still resolve incrementally; ancient ones rebuild
    assert table.append_range(table.version - 5) == (2, 2)
    assert table.append_range(0) is None


def test_out_of_band_mutation_raises():
    """Regression (ISSUE 5 satellite): mutating a column array without a
    version bump used to silently serve stale cached answers; now the
    fingerprint check in EvalCache._sync raises a clear error."""
    table = make_dataset("kdd", num_partitions=4, rows_per_partition=64)
    queries = WorkloadSpec(table, seed=2).sample_workload(4)
    store = AnswerStore(table, backend="host")
    store.get_batch(queries)
    col = table.schema[0].name
    table.columns[col][-1, -1] += 2.0  # out-of-band write, no version bump
    with pytest.raises(RuntimeError, match="without a version bump"):
        store.get_batch(queries)


def test_fingerprint_is_nan_stable():
    """A NaN sitting on a partition-boundary corner must not make the
    fingerprint unequal to itself (float NaN != NaN) — the guard fires
    only on real mutation."""
    table = make_dataset("kdd", num_partitions=4, rows_per_partition=64)
    col = table.numeric_columns[0]
    table.columns[col][0, 0] = np.nan
    cache = EvalCache(table, plane=None)
    cache.check_fingerprint()  # must not raise: nothing mutated
    cache.f32(col)
    table.columns[col][-1, -1] += 1.0  # a real out-of-band mutation
    with pytest.raises(RuntimeError, match="without a version bump"):
        cache.check_fingerprint()


def test_fingerprint_guard_amortized_but_inevitable():
    """Hot accessors only re-verify every FP_CHECK_EVERY syncs, so a
    mutation is still caught within a bounded number of calls even when
    no batch boundary forces the check."""
    table = make_dataset("kdd", num_partitions=4, rows_per_partition=64)
    col = table.numeric_columns[0]
    cache = EvalCache(table, plane=None)
    table.columns[col][0, 0] += 1.0
    with pytest.raises(RuntimeError, match="without a version bump"):
        for _ in range(EvalCache.FP_CHECK_EVERY + 1):
            cache.f32(col)


def test_old_nonfinite_routing_matches_cold_rebuild():
    """A column with inf in an OLD partition host-falls-back on the device
    backend; the append-delta evaluation must inherit that full-table
    routing (not re-decide from the finite delta rows), or merged sums
    would mix device f32 folds with the cold rebuild's host folds."""
    from repro.queries.ir import Aggregate, Clause, Predicate, Query

    table = make_dataset("kdd", num_partitions=6, rows_per_partition=64)
    col = table.numeric_columns[0]
    table.columns[col][0, 0] = np.inf  # pre-existing non-finite value
    q = Query(
        (Aggregate("sum", ((1.0, col),)),),
        Predicate.conjunction([Clause(table.numeric_columns[1], ">", 0.0)]),
    )
    store = AnswerStore(table, backend="device", plane=None)
    store.get_batch([q])
    append_partitions(table, _delta(2, seed=44))  # finite delta rows
    got = store.get_batch([q])
    assert store.carried == 1  # the entry survived and merged
    cold = per_partition_answers_batch(
        table, [q], backend="device", cache=EvalCache(table, plane=None)
    )
    assert_answers_equal(got, cold)


def test_nonfinite_delta_drops_device_answer_cache():
    """On the device backend a delta introducing inf flips per-query
    host-fallback routing, so the store must fall back to a full drop —
    and still serve answers equal to a cold evaluation."""
    table = make_dataset("kdd", num_partitions=4, rows_per_partition=64)
    queries = WorkloadSpec(table, seed=2).sample_workload(4)
    store = AnswerStore(table, backend="device", plane=None)
    store.get_batch(queries)
    delta = _delta(2, seed=43)
    delta.columns[delta.numeric_columns[0]][0, 0] = np.inf
    append_partitions(table, delta)
    got = store.get_batch(queries)
    assert store.carried == 0  # nothing merged: the cache was dropped
    cold = per_partition_answers_batch(
        table, queries, backend="device", cache=EvalCache(table, plane=None)
    )
    assert_answers_equal(got, cold)


def test_non_append_mutation_still_rebuilds_everything():
    """`with_layout`-style wholesale replacement (version bump without a
    log entry) must take the full-rebuild path in every store."""
    table = make_dataset("kdd", num_partitions=4, rows_per_partition=64)
    store = SketchStore(table, backend="host")
    shuffled = table.shuffled(seed=5)
    table.columns = shuffled.columns
    table.version += 1  # declared non-append mutation
    sk = store.sketches()
    assert store.full_rebuilds == 1 and store.incremental_updates == 0
    assert_sketches_equal(sk, build_sketches(table, backend="host"))


def test_concat_tables_pure_form_untouched():
    table = make_dataset("kdd", num_partitions=3, rows_per_partition=64)
    out = concat_tables([table, table])
    assert out is not table and out.num_partitions == 6
    assert table.version == 0 and out.version == 0 and out.append_log == {}


# --------------------------------------------------------------------------
# merge primitives under compaction-shaped inputs (lifecycle plane)
# --------------------------------------------------------------------------
def test_merge_discrete_span_cap_disqualification():
    """The span union disqualifies exactly at the width cap — the rule
    compaction's re-qualification shares with the append path."""
    cap = ingest.MAX_DISCRETE_WIDTH
    assert ingest.merge_discrete_span((0, 10), (5, 20)) == (0, 20)
    # union exactly at the cap still qualifies; one past it does not
    assert ingest.merge_discrete_span((0, cap - 1), (0, 0)) == (0, cap - 1)
    assert ingest.merge_discrete_span((0, cap), (0, 0)) is None
    assert ingest.merge_discrete_span((-4, 0), (cap - 4, cap - 4)) is None
    # a disqualified side poisons the union (and never un-poisons)
    assert ingest.merge_discrete_span(None, (0, 1)) is None
    assert ingest.merge_discrete_span((0, 1), None) is None


def test_fold_partition_spans_requalifies_survivors():
    """Per-partition spans re-fold after a gather: dropping the wide
    partition re-qualifies the survivors — a compact can only REqualify,
    never disqualify, because the survivor union is a subset."""
    wide = np.array([[0.0] * 31 + [float(ingest.MAX_DISCRETE_WIDTH)]])
    narrow = np.tile(np.arange(32, dtype=np.float64)[None, :], (3, 1))
    data = np.concatenate([narrow, wide], axis=0)
    spans = ingest.partition_int_spans(data)
    assert ingest.fold_partition_spans(spans) is None  # cap exceeded
    survivors = spans[:3]  # the compacted gather drops the wide partition
    assert ingest.fold_partition_spans(survivors) == (0, 32)
    # a non-integral partition stays disqualified through any gather
    frac = ingest.partition_int_spans(np.array([[0.5] * 4]))
    assert frac[0, 2] == 0
    assert ingest.fold_partition_spans(
        np.concatenate([survivors, frac], axis=0)
    ) is None


def test_akmv_union_duplicate_heavy_partitions():
    """K-min union over duplicate-heavy chunks — the shape compaction
    feeds the AKMV merge when most surviving rows share values: retained
    hash multiplicities must ADD exactly, bit-identical to one shot."""
    rng = np.random.default_rng(17)
    # 4 partitions, 300 rows, only 6 distinct values → every hash is
    # retained on both sides with large multiplicities
    col = rng.integers(0, 6, size=(4, 300)).astype(np.float64)
    for cut in (1, 150, 299):
        merged = merge_akmv_states(
            akmv_state(col[:, :cut]), akmv_state(col[:, cut:])
        )
        ndv, freq = akmv_finalize(merged)
        ndv0, freq0 = _akmv(col)
        np.testing.assert_array_equal(ndv, ndv0)
        np.testing.assert_array_equal(freq, freq0)
    # associativity across a 3-way merge (compaction folds many chunks)
    thirds = [col[:, :100], col[:, 100:200], col[:, 200:]]
    left = merge_akmv_states(
        merge_akmv_states(akmv_state(thirds[0]), akmv_state(thirds[1])),
        akmv_state(thirds[2]),
    )
    ndv, freq = akmv_finalize(left)
    np.testing.assert_array_equal(ndv, _akmv(col)[0])
    np.testing.assert_array_equal(freq, _akmv(col)[1])


def test_merge_primitives_accept_empty_partition_batches():
    """Zero-partition inputs (an empty append, or compacting everything
    but one slot) flow through every merge primitive without special
    cases and produce shape-correct empty results."""
    empty = np.empty((0, 64))
    width = len(ingest._MOMENT_MERGE)
    m = ingest.merge_moments(np.empty((0, width)), np.empty((0, width)))
    assert m.shape == (0, width)
    merged, lo = ingest.merge_bincounts(
        np.zeros((0, 5)), np.zeros((0, 3)), lo_a=2, lo_b=0
    )
    assert merged.shape == (0, 7) and lo == 0
    state = akmv_state(empty)
    h, c, d = merge_akmv_states(state, akmv_state(empty))
    assert h.shape[0] == 0 and c.shape[0] == 0 and d.shape == (0,)
    ndv, freq = akmv_finalize((h, c, d))
    assert ndv.shape == (0,) and freq.shape == (0, 4)
    assert ingest.partition_int_spans(empty).shape == (0, 3)
    assert ingest.fold_partition_spans(np.zeros((0, 3), np.int64)) is None
