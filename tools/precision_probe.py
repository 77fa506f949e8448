"""Measure what default f32 matmul precision costs at each dot site.

    PYTHONPATH=src python tools/precision_probe.py

On a TPU the default precision of an f32 matmul is a single bf16 pass.
For every float dot on the main path whose result feeds an exact
quantity (a per-partition sum or an integer group code), and for the
k-means distances, this computes the site's contraction on real
TPC-H-like lineitem columns (16 partitions × 16,384 rows, seed 0) at default
precision and as the code ships it, and compares both with float64
numpy:

  * the aggregate value projection ``einsum("qvc,cs->qvs")`` and the
    mixed-radix group-code projection ``einsum("qc,cs->qs")`` of
    `repro.queries.device` (four stacked queries, as one launch holds);
  * the Mosaic one-hot contraction inside the fused / group-aggregate
    kernels (`repro.kernels.common.contract_rows`);
  * the blocked one-hot oracle `repro.kernels.ref.blocked_onehot_aggregate`;
  * the k-means distance matmul `repro.core.clustering._pairwise_sq`
    (argmin disagreements with float64).

One line per site on stdout.  On a CPU every precision is full f32, so
both columns read the same there; the numbers mean something on a TPU.
"""
from __future__ import annotations

import functools
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from repro.core.clustering import _pairwise_sq  # noqa: E402
from repro.data.datasets import make_dataset  # noqa: E402
from repro.data.table import NUMERIC  # noqa: E402
from repro.kernels.common import LANE, SUBLANE, contract_rows, interpret  # noqa: E402
from repro.kernels.ref import blocked_onehot_aggregate  # noqa: E402

PARTITIONS, ROWS, SEED = 16, 16384, 0
HIGHEST = jax.lax.Precision.HIGHEST
# stacked group-bys: leading multipliers 525 and 875 need more than bf16's
# 8 significant bits; the last one's multipliers are all bf16-exact
GROUPBYS = (
    ("l_shipinstruct", "n1_name", "l_returnflag", "l_shipmode"),
    ("l_returnflag", "l_shipmode", "r1_name", "p_brand"),
    ("o_orderpriority", "l_shipmode", "n1_name", "l_returnflag"),
    ("l_partkey", "l_shipmode", "l_linestatus"),
)


def max_rel_err(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1e-30)))


def report(site: str, **readings) -> None:
    cells = ", ".join(f"{k} {v}" for k, v in readings.items())
    print(f"[probe] {site}: {cells}", flush=True)


def table_inputs():
    """→ (flat (C+1, S) f32 column stack with the ones column, the
    table's schema)."""
    table = make_dataset("tpch", num_partitions=PARTITIONS, rows_per_partition=ROWS,
                         seed=SEED, layout="sorted")
    cols = [np.asarray(table.columns[c.name], np.float32).reshape(-1) for c in table.schema]
    return np.stack(cols + [np.ones_like(cols[0])]), table.schema


def summable(flat, schema) -> np.ndarray:
    """Indices of the finite numeric columns and the ones column."""
    num = [i for i, c in enumerate(schema) if c.kind == NUMERIC] + [len(schema)]
    return np.asarray([i for i in num if np.isfinite(flat[i]).all()])


def probe_projections(flat, schema, rng) -> None:
    ncols1 = flat.shape[0]
    # values: each component one column (SUM(col)) or the ones column (COUNT)
    q, v = 4, 3
    coefs = np.zeros((q, v, ncols1), np.float32)
    live = summable(flat, schema)
    for i in range(q):
        for j in range(v):
            coefs[i, j, rng.choice(live)] = 1.0
    ref = np.einsum("qvc,cs->qvs", coefs.astype(np.float64), flat.astype(np.float64))
    out = {}
    for name, prec in (("default", None), ("HIGHEST (shipped)", HIGHEST)):
        f = jax.jit(functools.partial(jnp.einsum, "qvc,cs->qvs", precision=prec))
        out[name] = f"max rel err {max_rel_err(f(coefs, flat), ref):.3e}"
    report("value projection einsum", **out)

    # group codes: mixed-radix multipliers, as `queries.device` builds them
    index = {c.name: i for i, c in enumerate(schema)}
    mults = np.zeros((len(GROUPBYS), ncols1), np.float32)
    for i, groupby in enumerate(GROUPBYS):
        radix = 1
        for name in reversed(groupby):
            mults[i, index[name]] = radix
            radix *= schema[index[name]].cardinality
    exact = np.rint(np.einsum("qc,cs->qs", mults.astype(np.float64), flat.astype(np.float64)))
    out = {}
    for name, prec in (("default", None), ("HIGHEST (shipped)", HIGHEST)):
        f = jax.jit(functools.partial(jnp.einsum, "qc,cs->qs", precision=prec))
        got = np.rint(np.asarray(f(mults, flat), np.float64))
        out[name] = f"wrong codes {int((got != exact).sum())}/{exact.size}"
    lead = [int(m.max()) for m in mults]
    report(f"group-code einsum (leading multipliers {lead})", **out)


def _default_contract(values, onehot):
    """`contract_rows` at the matmul's default precision."""
    return jax.lax.dot_general(
        values, onehot.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _contract_kernel(vals_ref, codes_ref, o_ref, *, contract, groups):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bins = jax.lax.broadcasted_iota(jnp.int32, (groups, 1), 0)
    o_ref[0] += contract(vals_ref[0], codes_ref[0] == bins)


def mosaic_segment_sums(vals, codes, groups: int, contract, bt: int = 1024):
    """(P, 8, R) values, (P, 1, R) codes → (P, 8, groups) via a Pallas kernel."""
    p, v, r = vals.shape
    return pl.pallas_call(
        functools.partial(_contract_kernel, contract=contract, groups=groups),
        grid=(p, r // bt),
        in_specs=[
            pl.BlockSpec((1, v, bt), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, bt), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, v, groups), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, v, groups), jnp.float32),
        interpret=interpret(),
    )(vals, codes)


def probe_segment_sums(flat, schema, rng) -> None:
    sel = rng.choice(summable(flat, schema), size=SUBLANE, replace=True)
    p = flat.shape[1] // ROWS
    vals = flat[sel].reshape(SUBLANE, p, ROWS).transpose(1, 0, 2)  # (P, 8, R)
    groups = LANE  # l_partkey codes, folded onto one lane tile of groups
    k = next(i for i, c in enumerate(schema) if c.name == "l_partkey")
    codes = np.asarray(flat[k], np.int32).reshape(p, 1, ROWS) % groups
    ref = np.zeros((p, SUBLANE, groups))
    for i in range(p):
        for j in range(SUBLANE):
            ref[i, j] = np.bincount(codes[i, 0], weights=vals[i, j].astype(np.float64),
                                    minlength=groups)
    out = {}
    for name, fn in (("default", _default_contract), ("HIGHEST (shipped)", contract_rows)):
        got = jax.jit(functools.partial(mosaic_segment_sums, groups=groups, contract=fn))(
            vals, codes)
        out[name] = f"max rel err {max_rel_err(got, ref):.3e}"
    report("Mosaic one-hot contraction", **out)

    def onehot_default(values, c):
        onehot = (c[:, :, None] == jnp.arange(groups)).astype(jnp.float32)
        return jnp.einsum("pvr,prg->pvg", values, onehot)

    c2 = codes[:, 0, :]
    got_d = jax.jit(onehot_default)(vals, c2)
    got_h = jax.jit(functools.partial(blocked_onehot_aggregate, num_groups=groups))(vals, c2)
    report("blocked one-hot oracle",
           **{"default": f"max rel err {max_rel_err(got_d, ref):.3e}",
              "HIGHEST (shipped)": f"max rel err {max_rel_err(got_h, ref):.3e}"})


def probe_kmeans(rng) -> None:
    x = rng.normal(size=(1024, 48)).astype(np.float32)
    c = rng.normal(size=(64, 48)).astype(np.float32)
    ref = ((x[:, None, :].astype(np.float64) - c[None].astype(np.float64)) ** 2).sum(-1)
    got = np.asarray(jax.jit(_pairwise_sq)(x, c))
    flips = int((got.argmin(axis=1) != ref.argmin(axis=1)).sum())
    report("k-means distance matmul", **{"default (shipped)":
                                         f"argmin disagreements {flips}/{x.shape[0]}"})


def main() -> int:
    d = jax.devices()[0]
    print(f"[probe] platform {d.platform} ({d.device_kind}), "
          f"{PARTITIONS} x {ROWS} rows", flush=True)
    rng = np.random.default_rng(SEED)
    flat, schema = table_inputs()
    probe_projections(flat, schema, rng)
    probe_segment_sums(flat, schema, rng)
    probe_kmeans(rng)
    return 0


if __name__ == "__main__":
    sys.exit(main())
