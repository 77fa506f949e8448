"""Kernel ingest path vs host sketch builder parity (system invariant)."""
import numpy as np
import pytest

from repro.core.ingest import build_statistics, discrete_span
from repro.core.sketches import _akmv, _akmv_reference, build_sketches
from repro.data.datasets import make_dataset
from repro.data.table import NUMERIC

from test_query_device import edge_table


def test_kernel_ingest_matches_host_sketches():
    table = make_dataset("kdd", num_partitions=8, rows_per_partition=512)
    host = build_sketches(table)
    acc = build_statistics(table)
    for spec in table.schema:
        cs = host.columns[spec.name]
        if spec.kind == NUMERIC:
            got = acc[spec.name]["measures"]
            np.testing.assert_allclose(got, cs.measures, rtol=2e-4, atol=2e-4)
            # histogram counts: each equi-depth bucket holds ~rows/10
            counts = acc[spec.name]["hist_counts"]
            assert counts.shape == (8, 10)
            np.testing.assert_allclose(counts.sum(1), table.rows_per_partition)
        else:
            np.testing.assert_allclose(acc[spec.name]["counts"], cs.cat_counts, atol=0)


def test_kernel_ingest_ref_and_pallas_agree():
    table = make_dataset("aria", num_partitions=4, rows_per_partition=256)
    a = build_statistics(table, use_ref=False, discrete_counts=True)
    b = build_statistics(table, use_ref=True, discrete_counts=True)
    for col in a:
        for key in a[col]:
            np.testing.assert_allclose(a[col][key], b[col][key], rtol=2e-5, atol=2e-4)


def assert_sketches_match(host, dev):
    """Counts/HH/AKMV bit-identical; measures to float32 accumulation."""
    for name, cs in host.columns.items():
        d = dev.columns[name]
        np.testing.assert_allclose(d.measures, cs.measures, rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(d.ndv, cs.ndv)
        np.testing.assert_array_equal(d.dv_freq, cs.dv_freq)
        np.testing.assert_array_equal(d.hh_stats, cs.hh_stats)
        assert d.hh_items == cs.hh_items
        if cs.cat_counts is not None:
            np.testing.assert_array_equal(d.cat_counts, cs.cat_counts)
        if cs.hist_edges is not None:
            np.testing.assert_allclose(d.hist_edges, cs.hist_edges)
        if cs.bitmap is not None:
            np.testing.assert_array_equal(d.bitmap, cs.bitmap)
            np.testing.assert_array_equal(d.global_hh, cs.global_hh)


@pytest.mark.parametrize("use_ref", [True, False], ids=["xla-ref", "pallas"])
def test_build_sketches_device_matches_host(use_ref):
    table = make_dataset("kdd", num_partitions=8, rows_per_partition=512)
    assert_sketches_match(
        build_sketches(table, backend="host"),
        build_sketches(table, backend="device", use_ref=use_ref),
    )


@pytest.mark.parametrize("use_ref", [True, False], ids=["xla-ref", "pallas"])
def test_build_sketches_device_edge_cases(use_ref):
    """Rows % 128 != 0, constant / negative (log-masked) columns, and a
    cardinality-1 categorical — the padding/masking corners."""
    table = edge_table(parts=3, rows=200, seed=6)
    host = build_sketches(table, backend="host")
    dev = build_sketches(table, backend="device", use_ref=use_ref)
    assert_sketches_match(host, dev)
    # negative column: log-measure slots stay zero on both paths
    assert np.all(host.columns["neg"].measures[:, 5:] == 0)
    assert np.all(dev.columns["neg"].measures[:, 5:] == 0)
    # constant column: zero variance survives the f32 meansq - mean² form
    np.testing.assert_allclose(dev.columns["const"].measures[:, 4], 0.0, atol=1e-3)
    # cardinality-1 categorical: the single value is a 100% heavy hitter
    np.testing.assert_array_equal(dev.columns["one"].hh_stats[:, 0], 1.0)


def test_akmv_vectorized_matches_loop_reference():
    rng = np.random.default_rng(3)
    cases = [
        rng.normal(size=(5, 300)).astype(np.float32),  # ~all distinct (d > k)
        rng.integers(0, 9, size=(4, 257)).astype(np.int32),  # few distinct
        np.full((3, 130), 7.25, np.float32),  # constant (d = 1)
        rng.integers(0, 2, size=(2, 64)).astype(np.int32),  # r < k
    ]
    for col in cases:
        ndv, freq = _akmv(col)
        ndv_ref, freq_ref = _akmv_reference(col)
        np.testing.assert_allclose(ndv, ndv_ref, rtol=1e-12)
        np.testing.assert_allclose(freq, freq_ref, rtol=1e-12)


def test_discrete_span():
    assert discrete_span(np.asarray([[1.0, 4.0, 2.0]])) == (1, 4)
    assert discrete_span(np.asarray([[1.5, 4.0]])) is None
    assert discrete_span(np.asarray([[0.0, 1e6]])) is None


@pytest.mark.parametrize("use_ref", [True, False], ids=["xla-ref", "pallas"])
def test_measures_keep_small_variance_at_large_mean(use_ref):
    """A date-sorted partition: values ~1,000 with a spread of a few units
    over 16,384 rows.  Raw f32 Σx² (≈1.6e10) cannot carry that variance;
    the kernels' shifted power sums must."""
    from repro.core.ingest import measures_from_moments
    from repro.kernels import ops

    rng = np.random.default_rng(7)
    x = (1000.0 + np.sort(rng.integers(0, 3, size=(4, 16384)), axis=1)).astype(np.float32)
    raw = np.asarray(ops.moments_op(x, use_ref=use_ref))
    got = measures_from_moments(raw, x.shape[1], positive=True)
    xd = x.astype(np.float64)
    np.testing.assert_allclose(got[:, 0], xd.mean(1), rtol=1e-7)
    np.testing.assert_allclose(got[:, 3], (xd * xd).mean(1), rtol=1e-7)
    np.testing.assert_allclose(got[:, 4], xd.std(1), rtol=2e-4, atol=2e-4)
