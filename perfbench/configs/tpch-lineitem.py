"""TPC-H lineitem, denormalised as PS³'s TPC-H* (arXiv:2008.10569, §5.1.1).

Sixteen columns: the five numeric lineitem measures and eleven categorical
attributes of lineitem, part, nation, region and orders, with Zipf skew
whose popularity drifts along the ship date, sorted by ship date.  Every
domain is TPC-H's (clause 4.2.3) but l_partkey's, cut from SF x 200,000
keys to 200 (``l_partkey_codes`` in ``tpch-lineitem.json``).  The
columns are the ones ``repro.data.datasets.make_tpch_like`` makes, drawn
in the same order from the same seed; the benchmark keeps its own copy so
that no change to the program can change the data it is measured on.
"""
from __future__ import annotations

import numpy as np

from repro.data.table import CATEGORICAL, NUMERIC, ColumnSpec, from_flat

SORT_COLUMN = "l_shipdate"


def _zipf_codes(rng, n, cardinality, a=1.1):
    ranks = np.arange(1, cardinality + 1, dtype=np.float64)
    probs = ranks ** (-a)
    probs /= probs.sum()
    return rng.choice(cardinality, size=n, p=probs).astype(np.int32)


def _drifting_zipf(rng, phase, cardinality, a=1.1, drift=1.0):
    base = _zipf_codes(rng, phase.shape[0], cardinality, a).astype(np.int64)
    shift = np.floor(phase * cardinality * drift).astype(np.int64)
    return ((base + shift) % cardinality).astype(np.int32)


def build(seed: int, num_partitions: int, rows_per_partition: int):
    """The table for ``seed``, sorted by ship date, as a `repro` Table."""
    rng = np.random.default_rng(seed)
    n = num_partitions * rows_per_partition
    shipdate = np.sort(rng.integers(0, 2526, size=n))  # ~7 years of days
    phase = shipdate / 2526.0
    partkey = _drifting_zipf(rng, phase, 200, a=1.0, drift=0.6)
    quantity = rng.integers(1, 51, size=n).astype(np.float32)
    season = 1.0 + 0.5 * np.sin(2 * np.pi * shipdate / 365.0)
    base_price = (
        (900.0 + 10.0 * partkey + rng.gamma(2.0, 120.0, size=n)) * season
    ).astype(np.float32)
    discount = rng.choice(np.arange(0.0, 0.11, 0.01), size=n).astype(np.float32)
    tax = rng.choice(np.arange(0.0, 0.09, 0.01), size=n).astype(np.float32)
    extprice = (quantity * base_price).astype(np.float32)
    returnflag = np.where(
        rng.random(n) < np.clip(0.9 - 1.6 * phase, 0.02, 0.9),
        0,
        rng.integers(1, 3, size=n),
    ).astype(np.int32)
    cols = {
        "l_shipdate": shipdate.astype(np.float32),
        "l_quantity": quantity,
        "l_extendedprice": extprice,
        "l_discount": discount,
        "l_tax": tax,
        "l_partkey": partkey,
        "l_returnflag": returnflag,
        "l_linestatus": (phase > rng.random(n)).astype(np.int32),
        "l_shipmode": _drifting_zipf(rng, phase, 7, a=0.6, drift=0.4),
        "l_shipinstruct": rng.integers(0, 4, size=n).astype(np.int32),
        "n1_name": _drifting_zipf(rng, phase, 25, a=0.5, drift=0.3),
        "r1_name": rng.integers(0, 5, size=n).astype(np.int32),
        "p_brand": _drifting_zipf(rng, phase, 25, a=0.7, drift=0.8),
        "p_container": rng.integers(0, 40, size=n).astype(np.int32),
        "p_size": rng.integers(1, 51, size=n).astype(np.float32),
        "o_orderpriority": _drifting_zipf(rng, phase, 5, a=0.9, drift=0.5),
    }
    schema = (
        ColumnSpec("l_shipdate", NUMERIC),
        ColumnSpec("l_quantity", NUMERIC, positive=True),
        ColumnSpec("l_extendedprice", NUMERIC, positive=True),
        ColumnSpec("l_discount", NUMERIC),
        ColumnSpec("l_tax", NUMERIC),
        ColumnSpec("l_partkey", CATEGORICAL, 200),
        ColumnSpec("l_returnflag", CATEGORICAL, 3, groupable=True),
        ColumnSpec("l_linestatus", CATEGORICAL, 2, groupable=True),
        ColumnSpec("l_shipmode", CATEGORICAL, 7, groupable=True),
        ColumnSpec("l_shipinstruct", CATEGORICAL, 4, groupable=True),
        ColumnSpec("n1_name", CATEGORICAL, 25, groupable=True),
        ColumnSpec("r1_name", CATEGORICAL, 5, groupable=True),
        ColumnSpec("p_brand", CATEGORICAL, 25, groupable=True),
        ColumnSpec("p_container", CATEGORICAL, 40),
        ColumnSpec("p_size", NUMERIC, positive=True),
        ColumnSpec("o_orderpriority", CATEGORICAL, 5, groupable=True),
    )
    table = from_flat(schema, cols, name="tpch_like").repartitioned(num_partitions)
    return table.sorted_by(SORT_COLUMN)
