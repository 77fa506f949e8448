"""Error-bounded planner + unified QuerySpec/Session API (ISSUE 6).

The contract under test: `QueryPlanner.answer(q, error_bound=b)` reads
as few partitions as the stated relative error allows — escalating in
fixed-size chunks whose device compile census stays flat — and the
empirical error respects the bound on >= 90% of queries, on the host and
device backends and on 1/2/8-device partition meshes.  Around it:
`QuerySpec`/`Session` own the lifecycle (including consistency across
appends), `ViewStore` serves exact and upper-bound hybrid answers with
O(delta) maintenance, `AnswerStore.get_subset` keys partial answers by
partition-subset fingerprint (the escalation-round regression: a smaller
round's answer must never be served as a larger round's or as the full
answer), and every legacy kwarg signature keeps working behind a
`DeprecationWarning` shim with results identical to ``options=``.
CI runs this file in the forced 8-device lane too.
"""
import warnings
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import repro.api as api
import repro.planner.planner as planner_mod
from repro.backends import ExecOptions
from repro.core import ingest
from repro.core.features import FeatureBuilder
from repro.core.picker import (
    PickerConfig,
    build_training_data,
    train_picker,
)
from repro.core.sketches import SketchStore, build_sketches, update_sketches
from repro.data.datasets import make_dataset
from repro.data.table import Table, append_partitions
from repro.planner import QueryPlanner, ViewStore
from repro.planner.planner import PlannerConfig, _merge_raw
from repro.queries import device
from repro.queries.engine import (
    AnswerStore,
    EvalCache,
    per_partition_answers,
    per_partition_answers_batch,
)
from repro.queries.generator import WorkloadSpec
from repro.queries.ir import Aggregate, Clause, Predicate, Query
from repro.serving.engine import BatchPicker

HOST = ExecOptions(backend="host")
PLANES = (None, 2, 8)  # single-device path + real meshes
TINY_PICKER = PickerConfig(num_trees=8, tree_depth=3, feature_selection=False)


def _plane_or_skip(plane):
    if plane is not None and plane > len(jax.devices()):
        pytest.skip(f"needs {plane} devices, have {len(jax.devices())} "
                    "(CI sets XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return plane


def _rel_err(keys_e, est, keys_t, truth) -> float:
    """The benchmark's error metric: mean over truth groups × aggregates
    of the capped relative error; a missed group scores 1.0."""
    if keys_t.size == 0:
        return 0.0
    lut = {int(k): i for i, k in enumerate(keys_e)}
    tot, cnt = 0.0, 0
    for gi, k in enumerate(keys_t):
        i = lut.get(int(k))
        for j in range(truth.shape[1]):
            t = truth[gi, j]
            if np.isnan(t):
                continue
            if i is None or np.isnan(est[i, j]):
                tot += 1.0
            else:
                tot += min(abs(est[i, j] - t) / max(abs(t), 1e-12), 1.0)
            cnt += 1
    return tot / max(cnt, 1)


@pytest.fixture(scope="module")
def ctx():
    """One trained picker + held-out queries, shared read-only."""
    table = make_dataset("tpch", num_partitions=48, rows_per_partition=96)
    art = train_picker(table, WorkloadSpec(table, seed=0),
                       num_train_queries=24, config=TINY_PICKER, options=HOST)
    queries = WorkloadSpec(table, seed=123).sample_workload(10)
    truth = {q.describe(): per_partition_answers(table, q, options=HOST)
             for q in queries}
    return SimpleNamespace(table=table, art=art, queries=queries, truth=truth)


def _planner(ctx, options, views=None):
    return QueryPlanner(
        ctx.art.picker, AnswerStore(ctx.table, options=options), views=views
    )


# --------------------------------------------------------------------------
# the tentpole: error-bound calibration on every backend/mesh
# --------------------------------------------------------------------------
@pytest.mark.parametrize("plane", PLANES, ids=["single", "mesh2", "mesh8"])
@pytest.mark.parametrize("backend", ["host", "device"])
def test_calibration_sweep(ctx, backend, plane):
    """Empirical error ≤ the stated bound on ≥ 90% of held-out queries."""
    _plane_or_skip(plane)
    if backend == "host" and plane is not None:
        pytest.skip("the host backend has no mesh axis")
    planner = _planner(ctx, ExecOptions(backend=backend, mesh=plane))
    queries = ctx.queries if backend == "host" else ctx.queries[:6]
    bound = 0.05
    hits = 0
    for q in queries:
        pa = planner.answer(q, error_bound=bound)
        ta = ctx.truth[q.describe()]
        err = _rel_err(pa.group_keys, pa.estimate, ta.group_keys, ta.truth())
        hits += err <= bound
        assert pa.partitions_read <= ctx.table.num_partitions
        assert np.all(pa.ci_halfwidth >= 0)
    assert hits / len(queries) >= 0.9, f"{hits}/{len(queries)} within {bound}"


def test_escalation_monotonic(ctx):
    """Tighter bounds never read fewer partitions, and within one plan the
    cumulative schedule grows monotonically round over round."""
    planner = _planner(ctx, HOST)
    reads = {}
    for bound in (0.02, 0.05, 0.20):
        total = 0
        for q in ctx.queries:
            pa = planner.answer(q, error_bound=bound)
            sched = pa.plan.schedule
            assert pa.plan.rounds == len(sched)
            assert all(a <= b for a, b in zip(sched, sched[1:])), sched
            assert pa.partitions_read >= (sched[-1] if sched else 0)
            total += pa.partitions_read
        reads[bound] = total
    assert reads[0.02] >= reads[0.05] >= reads[0.20], reads


def test_exact_mode_when_bound_unreachable_by_sampling(ctx):
    """A near-zero bound escalates until everything is read: mode 'exact',
    zero halfwidths, estimate equal to the truth."""
    planner = _planner(ctx, HOST)
    q = next(q for q in ctx.queries if q.groupby)
    pa = planner.answer(q, error_bound=1e-4)
    ta = ctx.truth[q.describe()]
    if pa.plan.mode == "exact":
        assert np.all(pa.ci_halfwidth == 0)
    assert _rel_err(pa.group_keys, pa.estimate, ta.group_keys, ta.truth()) <= 1e-3
    assert set(ta.group_keys) <= set(pa.group_keys)


def test_budget_mode_single_round(ctx):
    planner = _planner(ctx, HOST)
    q = ctx.queries[0]
    pa = planner.answer(q, budget=12)
    assert pa.plan.rounds == 1 and pa.plan.budget == 12
    assert 0 < pa.partitions_read <= ctx.table.num_partitions
    with pytest.raises(ValueError, match="exactly one"):
        planner.answer(q, error_bound=0.05, budget=12)
    with pytest.raises(ValueError, match="exactly one"):
        planner.answer(q)


def test_empty_candidates_short_circuit(ctx):
    """A predicate no partition can satisfy answers from sketches alone."""
    planner = _planner(ctx, HOST)
    col = ctx.table.numeric_columns[0]
    q = Query((Aggregate("count"),),
              Predicate.conjunction([Clause(col, ">", 1e15)]),
              (ctx.table.groupable_columns[0],))
    pa = planner.answer(q, error_bound=0.05)
    assert pa.plan.mode == "empty" and pa.partitions_read == 0
    assert pa.group_keys.size == 0 and pa.estimate.size == 0


def test_answer_deterministic_and_cached(ctx):
    """Same query + bound twice: identical answer, second pass all cache
    hits (prefix reads are keyed by subset fingerprint)."""
    planner = _planner(ctx, HOST)
    q = ctx.queries[1]
    a = planner.answer(q, error_bound=0.05)
    misses0 = planner.answers.misses
    b = planner.answer(q, error_bound=0.05)
    assert planner.answers.misses == misses0  # every chunk re-served
    assert np.array_equal(a.group_keys, b.group_keys)
    assert np.array_equal(a.estimate, b.estimate)
    assert a.partitions_read == b.partitions_read


def test_merge_raw_keeps_rows_of_groupless_chunks():
    """Regression: a chunk that saw zero occupied groups still read rows;
    dropping them desynced row indices from the accumulated raw tensor."""
    raw_a = np.zeros((3, 0, 2))  # 3 partitions read, no groups seen
    keys_b = np.asarray([4, 7], np.int64)
    raw_b = np.ones((2, 2, 2))
    keys, raw = _merge_raw(np.empty(0, np.int64), raw_a, keys_b, raw_b)
    assert raw.shape == (5, 2, 2)
    assert np.all(raw[:3] == 0) and np.all(raw[3:] == 1)
    keys2, raw2 = _merge_raw(keys, raw, np.empty(0, np.int64), np.zeros((1, 0, 2)))
    assert raw2.shape == (6, 2, 2) and np.array_equal(keys2, keys)


def _pairwise_merge(keys_a, raw_a, keys_b, raw_b):
    """The fold one chunk at a time: a new union and array per pair."""
    keys = np.union1d(keys_a, keys_b)
    raw = np.zeros((raw_a.shape[0] + raw_b.shape[0], keys.shape[0], raw_b.shape[2]))
    if keys_a.size:
        raw[: raw_a.shape[0], np.searchsorted(keys, keys_a)] = raw_a
    if keys_b.size:
        raw[raw_a.shape[0]:, np.searchsorted(keys, keys_b)] = raw_b
    return keys, raw


def _left_fold(merge, keys, raw, *blocks):
    for k, r in zip(blocks[0::2], blocks[1::2]):
        keys, raw = merge(keys, raw, k, r)
    return keys, raw


def _chunk_blocks(rng, n, channels):
    """``n`` chunk reads' (group keys, raw) as `get_subset` returns them:
    in turn groupless, disjoint from every other block's codes, and two
    drawn from one shared range of codes; float32 and float64 raw."""
    blocks = []
    for b in range(n):
        rows = int(rng.integers(1, 17))
        if b % 4 == 0:
            keys = np.empty(0, np.int64)
        elif b % 4 == 1:
            keys = np.unique(rng.integers(1000 * b, 1000 * b + 50, size=20))
        else:
            keys = np.unique(rng.integers(0, 64, size=int(rng.integers(1, 40))))
        raw = rng.standard_normal((rows, keys.size, channels))
        blocks += [keys.astype(np.int64), raw.astype(np.float32) if b % 2 else raw]
    return blocks


@pytest.mark.parametrize("start", ["empty", "nonempty"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_merge_raw_in_one_call_equals_the_pairwise_left_fold(seed, start):
    """One `_merge_raw` call over a state and many chunk blocks gives, bit
    for bit, the keys and raw of folding the blocks in one at a time."""
    rng = np.random.default_rng(seed)
    channels = 3
    state = (np.empty(0, np.int64), np.zeros((0, 0, channels)))
    if start == "nonempty":
        state = _left_fold(_pairwise_merge, *state, *_chunk_blocks(rng, 3, channels))
    blocks = _chunk_blocks(rng, 12, channels)
    keys, raw = _merge_raw(*state, *blocks)
    for fold in (_pairwise_merge, _merge_raw):
        want_keys, want_raw = _left_fold(fold, *state, *blocks)
        assert keys.dtype == want_keys.dtype and np.array_equal(keys, want_keys)
        assert raw.dtype == want_raw.dtype and raw.shape == want_raw.shape
        assert raw.tobytes() == want_raw.tobytes()
    rows = state[1].shape[0] + sum(r.shape[0] for r in blocks[1::2])
    assert raw.shape[0] == rows  # groupless blocks kept their rows


def test_one_merge_per_read_answers_as_the_pairwise_fold(ctx, monkeypatch):
    """Error-bounded answers over several rounds are bit-identical whether
    each `_read` call folds its chunks in one `_merge_raw` call or one at
    a time, and ``plan.merges`` counts `_read` calls, not chunk reads."""
    cfg = PlannerConfig(chunk=4)  # several chunks per `_read` call
    cases = [(q, bound) for bound in (0.02, 0.1)
             for q in [q for q in ctx.queries if q.groupby][:3]]
    sess = api.Session(ctx.table, options=HOST, planner_config=cfg)
    sess.planner = QueryPlanner(ctx.art.picker, sess.answers, views=sess.views,
                                config=cfg)
    read_calls = []
    read = QueryPlanner._read

    def counted(self, *args):
        read_calls[-1] += 1
        return read(self, *args)

    with monkeypatch.context() as mp:
        mp.setattr(QueryPlanner, "_read", counted)
        got = []
        for q, bound in cases:
            read_calls.append(0)
            got.append(sess.planner.answer(q, error_bound=bound))
    monkeypatch.setattr(planner_mod, "_merge_raw",
                        lambda *pairs: _left_fold(_pairwise_merge, *pairs))
    planner = QueryPlanner(ctx.art.picker, AnswerStore(ctx.table, options=HOST),
                           config=cfg)
    for a, (q, bound), n_reads in zip(got, cases, read_calls):
        b = planner.answer(q, error_bound=bound)
        np.testing.assert_array_equal(a.group_keys, b.group_keys)
        np.testing.assert_array_equal(a.estimate, b.estimate)
        np.testing.assert_array_equal(a.ci_halfwidth, b.ci_halfwidth)
        assert a.partitions_read == b.partitions_read
        assert a.plan.schedule == b.plan.schedule
        assert a.plan.merges == n_reads
    assert min(a.plan.rounds for a in got) > 1
    assert {a.plan.mode for a in got} == {"exact", "sampled"}
    assert sum(a.plan.merges for a in got) < sum(a.plan.chunk_reads for a in got)
    stats = sess.stats()
    assert stats["merges"] == sum(a.plan.merges for a in got)
    assert stats["chunk_evals"] == sum(a.plan.chunk_reads for a in got)


def test_census_flat_across_escalation(ctx):
    """Device-backend escalation compiles at most the chunk-shape census
    of the distinct query signatures, independent of rounds or bounds."""
    planner = _planner(ctx, ExecOptions(backend="device"))
    chunk = planner.config.chunk
    sub = Table(ctx.table.schema,
                {k: v[:chunk] for k, v in ctx.table.columns.items()},
                name=f"{ctx.table.name}/censusprobe")
    probes = [q for q in ctx.queries if q.groupby][:2]
    expected = set()
    for q in probes:
        expected |= device.workload_census(sub, [q])
    device.TRACES.reset()
    rounds = 0
    for q in probes:
        for bound in (0.10, 0.05):
            rounds += planner.answer(q, error_bound=bound).plan.rounds
    assert device.TRACES.total() <= len(expected), (
        device.TRACES.counts(), expected, rounds)


# --------------------------------------------------------------------------
# AnswerStore.get_subset: the escalation-round partial-answer regression
# --------------------------------------------------------------------------
def _small(parts=10, rows=64, seed=0):
    table = make_dataset("kdd", num_partitions=parts, rows_per_partition=rows,
                         seed=seed)
    queries = WorkloadSpec(table, seed=3).sample_workload(4)
    return table, queries


def test_get_subset_matches_cold_eval_in_id_order():
    table, queries = _small()
    store = AnswerStore(table, options=HOST)
    q = queries[0]
    ids = np.asarray([7, 2, 5], np.int64)
    ans = store.get_subset(q, ids)
    full = per_partition_answers(table, q, options=HOST)
    assert ans.raw.shape[0] == ids.size
    # rows come back in part_ids order; totals agree with the full answers
    pos = np.searchsorted(full.group_keys, ans.group_keys)
    assert np.array_equal(full.group_keys[pos], ans.group_keys)
    np.testing.assert_allclose(ans.raw, full.raw[ids][:, pos], rtol=1e-12)
    # a different order is a different fingerprint with permuted rows
    perm = store.get_subset(q, ids[::-1])
    np.testing.assert_allclose(perm.raw, ans.raw[::-1], rtol=1e-12)


def test_subset_answers_never_served_as_full():
    """The ISSUE-6 bugfix: partials live in their own fingerprint-keyed
    cache, so a smaller round's answer can never leak into a larger
    round's read or into the full answer."""
    table, queries = _small()
    store = AnswerStore(table, options=HOST)
    q = queries[0]
    small = store.get_subset(q, np.arange(4))
    misses0 = store.misses
    big = store.get_subset(q, np.arange(8))
    assert store.misses == misses0 + 1  # distinct subset: evaluated fresh
    assert small.raw.shape[0] == 4 and big.raw.shape[0] == 8
    full = store.get(q)
    assert full.raw.shape[0] == table.num_partitions
    # re-reads of either subset are hits, still shape-correct
    hits0 = store.hits
    assert store.get_subset(q, np.arange(4)).raw.shape[0] == 4
    assert store.hits == hits0 + 1


def test_get_subset_slices_from_cached_full_answer():
    table, queries = _small()
    store = AnswerStore(table, options=HOST)
    q = queries[1]
    full = store.get(q)
    misses0, hits0 = store.misses, store.hits
    ids = np.asarray([1, 3, 8], np.int64)
    sub = store.get_subset(q, ids)
    assert (store.misses, store.hits) == (misses0, hits0 + 1)
    assert np.array_equal(sub.group_keys, full.group_keys)
    assert np.array_equal(sub.raw, full.raw[ids])


def test_partials_survive_pure_appends_only():
    table, queries = _small()
    store = AnswerStore(table, options=HOST)
    q = queries[0]
    ids = np.arange(5)
    store.get_subset(q, ids)
    delta = make_dataset("kdd", num_partitions=2, rows_per_partition=64,
                         layout="random", seed=9)
    append_partitions(table, delta)  # pure append: old partitions untouched
    hits0, misses0 = store.hits, store.misses
    store.get_subset(q, ids)
    assert (store.hits, store.misses) == (hits0 + 1, misses0)
    table.version += 1  # declared non-append mutation: partials must drop
    store.get_subset(q, ids)
    assert store.misses == misses0 + 1


# --------------------------------------------------------------------------
# ViewStore: exact answers, upper bounds, O(delta) maintenance
# --------------------------------------------------------------------------
def _view_setup(parts=10):
    table, _ = _small(parts=parts)
    gcol = table.groupable_columns[0]
    pos = next(s.name for s in table.schema if getattr(s, "positive", False))
    aggs = (Aggregate("count"), Aggregate("sum", ((1.0, pos),)))
    return table, gcol, aggs


def test_view_exact_answer_matches_engine_truth():
    table, gcol, aggs = _view_setup()
    views = ViewStore(table, options=HOST)
    views.register((gcol,), aggs)
    card = table.spec(gcol).cardinality
    for pred in (Predicate(),
                 Predicate.conjunction([Clause(gcol, "<", max(card // 2, 1))])):
        q = Query(aggs, pred, (gcol,))
        hit = views.answer(q)
        assert hit is not None
        keys, est = hit
        ta = per_partition_answers(table, q, options=HOST)
        truth = ta.truth()
        occupied = ~np.isnan(truth[:, 0])
        assert np.array_equal(keys, ta.group_keys[occupied])
        np.testing.assert_allclose(est, truth[occupied], rtol=1e-9)
    # a predicate on a non-view column cannot be answered exactly
    ncol = table.numeric_columns[0]
    q = Query(aggs, Predicate.conjunction([Clause(ncol, ">", 0.0)]), (gcol,))
    assert views.answer(q) is None


def test_view_upper_bounds_cap_truth():
    table, gcol, aggs = _view_setup()
    views = ViewStore(table, options=HOST)
    views.register((gcol,), aggs)
    ncol = table.numeric_columns[0]
    med = float(np.median(table.columns[ncol]))
    q = Query(aggs, Predicate.conjunction([Clause(ncol, ">", med)]), (gcol,))
    caps = views.upper_bounds(q)
    assert caps is not None
    cap_keys, cap_vals = caps
    ta = per_partition_answers(table, q, options=HOST)
    truth = ta.truth()
    for gi, k in enumerate(ta.group_keys):
        if np.isnan(truth[gi, 0]):
            continue
        # every group with passing rows is in the capped set, under its cap
        i = int(np.searchsorted(cap_keys, k))
        assert i < cap_keys.size and cap_keys[i] == k
        assert np.all(truth[gi] <= cap_vals[i] + 1e-9)


def test_view_incremental_update_matches_fresh_rebuild():
    table, gcol, aggs = _view_setup()
    views = ViewStore(table, options=HOST)
    views.register((gcol,), aggs)
    delta = make_dataset("kdd", num_partitions=3, rows_per_partition=64,
                         layout="random", seed=11)
    append_partitions(table, delta)
    q = Query(aggs, Predicate(), (gcol,))
    keys, est = views.answer(q)  # triggers refresh
    assert views.incremental_updates == 1 and views.full_rebuilds == 0
    fresh = ViewStore(table, options=HOST)
    fresh.register((gcol,), aggs)
    fkeys, fest = fresh.answer(q)
    assert np.array_equal(keys, fkeys)
    np.testing.assert_allclose(est, fest, rtol=1e-9)
    # a non-append mutation forces the full-rebuild path
    table.version += 1
    views.answer(q)
    assert views.full_rebuilds == 1


def test_view_register_validates_columns():
    table, gcol, aggs = _view_setup()
    views = ViewStore(table, options=HOST)
    with pytest.raises(ValueError, match="non-categorical"):
        views.register((table.numeric_columns[0],), aggs)


# --------------------------------------------------------------------------
# QuerySpec / Session facade
# --------------------------------------------------------------------------
def _mk_query(table):
    gcol = table.groupable_columns[0]
    return Query((Aggregate("count"),), Predicate(), (gcol,))


def test_queryspec_exactly_one_contract():
    q = _mk_query(_small(parts=4)[0])
    with pytest.raises(ValueError, match="exactly one"):
        api.QuerySpec(q)
    with pytest.raises(ValueError, match="exactly one"):
        api.QuerySpec(q, error_bound=0.05, budget=4)
    with pytest.raises(ValueError, match="error_bound"):
        api.QuerySpec(q, error_bound=1.5)
    with pytest.raises(ValueError, match="latency_bound"):
        api.QuerySpec(q, latency_bound=0.0)
    with pytest.raises(ValueError, match="budget"):
        api.QuerySpec(q, budget=0)
    assert api.QuerySpec(q, error_bound=0.05).error_bound == 0.05


@pytest.fixture(scope="module")
def session():
    table = make_dataset("kdd", num_partitions=16, rows_per_partition=64)
    sess = api.Session(table, options=HOST)
    sess.prepare(WorkloadSpec(table, seed=1), num_train_queries=10,
                 picker_config=TINY_PICKER)
    return sess


def test_session_requires_prepare():
    table, _ = _small(parts=4)
    sess = api.Session(table, options=HOST)
    with pytest.raises(RuntimeError, match="prepare"):
        sess.execute(_mk_query(table))


def test_session_execute_contracts(session):
    q = _mk_query(session.table)
    # a bare Query defaults to the 5% error-bound contract
    ans = session.execute(q)
    assert ans.plan.error_bound == 0.05
    ans = session.execute(api.QuerySpec(q, budget=6))
    assert ans.plan.budget == 6 and ans.plan.rounds == 1
    # latency bound converts through the read-rate EMA (one chunk before
    # any observation exists, rate-derived afterwards)
    ans = session.execute(api.QuerySpec(q, latency_bound=0.5))
    assert ans.plan.budget >= 1
    stats = session.stats()
    assert stats["executed"] == 3 and stats["read_rate_ema"] is not None
    assert stats["num_partitions"] == session.table.num_partitions


def test_session_view_mode(session):
    q = _mk_query(session.table)
    session.register_view(q.groupby, q.aggregates)
    ans = session.execute(api.QuerySpec(q, error_bound=0.05))
    assert ans.plan.mode == "view" and ans.partitions_read == 0
    assert np.all(ans.ci_halfwidth == 0)
    ta = per_partition_answers(session.table, q, options=HOST)
    assert _rel_err(ans.group_keys, ans.estimate, ta.group_keys, ta.truth()) < 1e-9


def test_session_stays_consistent_across_appends():
    table, _ = _small(parts=12)
    sess = api.Session(table, options=HOST)
    sess.prepare(WorkloadSpec(table, seed=1), num_train_queries=8,
                 picker_config=TINY_PICKER)
    q = _mk_query(table)
    sess.execute(api.QuerySpec(q, error_bound=0.10))
    delta = make_dataset("kdd", num_partitions=3, rows_per_partition=64,
                         layout="random", seed=21)
    append_partitions(table, delta)
    # features refresh from the incrementally updated sketches: a full-read
    # answer on the grown table matches the grown-table truth exactly
    ans = sess.execute(api.QuerySpec(q, budget=table.num_partitions))
    assert sess._fb_version == table.version
    assert ans.plan.candidates <= table.num_partitions
    ta = per_partition_answers(table, q, options=HOST)
    assert _rel_err(ans.group_keys, ans.estimate, ta.group_keys, ta.truth()) < 1e-9


# --------------------------------------------------------------------------
# deprecation shims: every migrated signature warns AND matches options=
# --------------------------------------------------------------------------
def _sk_eq(a, b):
    for name, ca in a.columns.items():
        cb = b.columns[name]
        assert np.array_equal(ca.measures, cb.measures), name
        assert (ca.ndv is None) == (cb.ndv is None), name
        if ca.ndv is not None:
            assert np.array_equal(ca.ndv, cb.ndv), name


def _stats_eq(a, b):
    assert set(a) == set(b)
    for col in a:
        assert set(a[col]) == set(b[col]), col
        for key in a[col]:
            assert np.array_equal(np.asarray(a[col][key]),
                                  np.asarray(b[col][key])), (col, key)


def test_shim_sketch_entry_points():
    table, _ = _small(parts=6)
    new = build_sketches(table, options=HOST)
    with pytest.warns(DeprecationWarning):
        legacy = build_sketches(table, backend="host")
    _sk_eq(legacy, new)
    with pytest.warns(DeprecationWarning):
        store = SketchStore(table, backend="host")
    _sk_eq(store.sketches(), new)
    start = table.num_partitions
    append_partitions(table, make_dataset("kdd", num_partitions=2,
                                          rows_per_partition=64, seed=8,
                                          layout="random"))
    with pytest.warns(DeprecationWarning):
        legacy_up = update_sketches(new, table, start, backend="host")
    _sk_eq(legacy_up, update_sketches(new, table, start, options=HOST))


def test_shim_statistics_entry_points():
    table, _ = _small(parts=6)
    new = ingest.build_statistics(table, options=ExecOptions(mesh=None))
    with pytest.warns(DeprecationWarning):
        legacy = ingest.build_statistics(table, plane=None)
    _stats_eq(legacy, new)
    start = 3
    with pytest.warns(DeprecationWarning):
        legacy_d = ingest.delta_statistics(table, start, plane=None)
    _stats_eq(legacy_d, ingest.delta_statistics(table, start,
                                                options=ExecOptions(mesh=None)))


def test_shim_eval_entry_points():
    table, queries = _small(parts=6)
    q = queries[0]
    new = per_partition_answers(table, q, options=HOST)
    with pytest.warns(DeprecationWarning):
        legacy = per_partition_answers(table, q, backend="host")
    assert np.array_equal(legacy.raw, new.raw)
    with pytest.warns(DeprecationWarning):
        cache = EvalCache(table, plane=None)
    with pytest.warns(DeprecationWarning):
        legacy_b = per_partition_answers_batch(table, queries, backend="host",
                                               cache=cache, use_ref=False)
    new_b = per_partition_answers_batch(table, queries, options=HOST)
    for a, b in zip(legacy_b, new_b):
        assert np.array_equal(a.raw, b.raw)
    with pytest.warns(DeprecationWarning):
        store = AnswerStore(table, backend="host")
    assert np.array_equal(store.get(q).raw, new.raw)


def test_shim_training_entry_points():
    table, _ = _small(parts=6)
    wl = WorkloadSpec(table, seed=2)
    cfg = PickerConfig(num_trees=4, tree_depth=2, feature_selection=False)
    new_art = train_picker(table, wl, num_train_queries=6, config=cfg,
                           options=HOST)
    with pytest.warns(DeprecationWarning):
        legacy_art = train_picker(table, wl, num_train_queries=6, config=cfg,
                                  backend="host")
    q = new_art.queries[0]
    a = new_art.picker.pick(q, 4)
    b = legacy_art.picker.pick(q, 4)
    assert np.array_equal(a.ids, b.ids) and np.array_equal(a.weights, b.weights)
    fb = FeatureBuilder(table, build_sketches(table, options=HOST))
    with pytest.warns(DeprecationWarning):
        lf, lc, _ = build_training_data(table, fb, new_art.queries[:3],
                                        backend="host")
    nf, nc, _ = build_training_data(table, fb, new_art.queries[:3],
                                    options=HOST)
    for x, y in zip(lc, nc):
        assert np.array_equal(x, y)
    with pytest.warns(DeprecationWarning):
        server = BatchPicker(new_art.picker, backend="host")
    sel = server.pick_batch([q], 4)[0]
    assert np.array_equal(sel.ids, a.ids)


def test_options_and_legacy_together_raise():
    table, _ = _small(parts=4)
    with pytest.raises(ValueError, match="both"):
        build_sketches(table, backend="host", options=HOST)


def test_options_path_emits_no_deprecation_warnings():
    """The migrated internal surface is silent — the Session flow end to
    end under `error` warning filters."""
    table, _ = _small(parts=8)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        sess = api.Session(table, options=HOST)
        sess.prepare(WorkloadSpec(table, seed=1), num_train_queries=6,
                     picker_config=PickerConfig(num_trees=4, tree_depth=2,
                                                feature_selection=False))
        sess.register_view((table.groupable_columns[0],),
                           (Aggregate("count"),))
        sess.execute(api.QuerySpec(_mk_query(table), error_bound=0.10))
