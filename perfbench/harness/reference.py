"""Plain numpy answers of an instantiated query, without the program.

Semantics, as the configurations state them:

* a clause compares a column with its constant cast to the column's type
  (float32 columns compare in float32; codes compare as integers; ``in``
  is membership); a row passes when every OR-group has a clause it meets;
* the group of a row is the mixed-radix code of its group-by columns, the
  first column most significant;
* per partition and group: the passing-row count, and for each SUM or AVG,
  the sum of its linear projection over the passing rows (an AVG is that
  sum over the count).  COUNT adds no channel.

Sums are taken in float64 over the float32 values.  ``scale`` is the sum of
``|w|·|x|`` over the same rows and terms: the size a float32 accumulation
of that sum makes its rounding error relative to.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_CMP = {
    "<": np.less, "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal,
    "==": np.equal, "!=": np.not_equal,
}


@dataclasses.dataclass
class Dense:
    """Per-partition answers over every group code of the radix."""

    radix: int
    count: np.ndarray  # (P, radix) int64
    sums: np.ndarray  # (P, radix, channels) float64
    scale: np.ndarray  # (P, radix, channels) float64
    kinds: list[str]  # per aggregate: count | sum | avg

    def occupied(self, parts=None) -> np.ndarray:
        c = self.count if parts is None else self.count[parts]
        return np.flatnonzero(c.sum(axis=0))

    def exact(self) -> tuple[np.ndarray, np.ndarray]:
        """(group keys, (G, aggregates) values) over the whole table."""
        keys = self.occupied()
        cnt = self.count.sum(axis=0)[keys].astype(np.float64)
        sums = self.sums.sum(axis=0)[keys]
        out = np.zeros((keys.size, len(self.kinds)))
        ch = 0
        for j, kind in enumerate(self.kinds):
            if kind == "count":
                out[:, j] = cnt
                continue
            out[:, j] = sums[:, ch] if kind == "sum" else sums[:, ch] / cnt
            ch += 1
        return keys, out


def row_mask(columns: dict, where: list) -> np.ndarray:
    shape = next(iter(columns.values())).shape
    mask = np.ones(shape, bool)
    for group in where:
        gmask = np.zeros(shape, bool)
        for col, op, v in group:
            x = columns[col]
            if op == "in":
                gmask |= np.isin(x, np.asarray(v, dtype=x.dtype))
            else:
                gmask |= _CMP[op](x, x.dtype.type(v))
        mask &= gmask
    return mask


def projections(columns: dict, rows: np.ndarray, terms: list,
                rounding=None) -> tuple[np.ndarray, np.ndarray]:
    """(value, scale) of one SUM/AVG's projection on the flat ``rows``.
    With ``rounding`` (a control) the value is the float32 projection after
    ``rounding``; without it, the exact float64 one."""
    value = np.zeros(rows.size, np.float64)
    scale = np.zeros_like(value)
    for w, col in terms:
        x = columns[col].reshape(-1)[rows].astype(np.float64)
        value += w * x
        scale += abs(w) * np.abs(x)
    if rounding is not None:
        v32 = np.zeros(rows.size, np.float32)
        for w, col in terms:
            v32 += np.float32(w) * columns[col].reshape(-1)[rows].astype(np.float32)
        value = rounding(v32).astype(np.float64)
    return value, scale


def dense(columns: dict, cardinality: dict, query: dict, rounding=None) -> Dense:
    """Answers of ``query`` for every partition of ``columns`` ((P, R) arrays)."""
    mask = row_mask(columns, query["where"])
    p, r = mask.shape
    rows = np.flatnonzero(mask.reshape(-1))
    seg = rows // r
    radix = 1
    for g in query["groupby"]:
        seg = seg * cardinality[g] + columns[g].reshape(-1)[rows].astype(np.int64)
        radix *= cardinality[g]
    # seg = partition * radix + group code, with the first group-by column
    # most significant inside the code
    count = np.bincount(seg, minlength=p * radix).reshape(p, radix)
    sums, scales = [], []
    for kind, terms in query["aggregates"]:
        if kind == "count":
            continue
        value, scale = projections(columns, rows, terms, rounding)
        sums.append(np.bincount(seg, weights=value, minlength=p * radix))
        scales.append(np.bincount(seg, weights=scale, minlength=p * radix))
    n = len(sums)
    shape = (p, radix, n)
    return Dense(
        radix,
        count,
        np.stack(sums, axis=-1).reshape(shape) if n else np.zeros(shape),
        np.stack(scales, axis=-1).reshape(shape) if n else np.zeros(shape),
        [kind for kind, _ in query["aggregates"]],
    )


def relative_error(truth_keys, truth, est_keys, est) -> float:
    """PS³'s error of an estimate: the mean over the exact answer's groups
    and aggregates of min(|est - exact| / |exact|, 1), where a group the
    estimate misses (or holds as NaN) counts 1, and an exact value of zero
    counts 0 only where the estimate is zero too."""
    if truth.size == 0:
        return 0.0
    est = np.asarray(est, np.float64)
    if est.ndim != 2 or est.shape[1] != truth.shape[1]:
        return 1.0  # an answer of another shape answers another query
    rel = np.ones_like(truth)
    pos = {int(k): j for j, k in enumerate(est_keys)}
    for gi, k in enumerate(truth_keys):
        j = pos.get(int(k))
        if j is None or np.isnan(est[j, 0]):
            continue
        t, e = truth[gi], est[j]
        with np.errstate(invalid="ignore", divide="ignore"):
            r = np.abs(e - t) / np.abs(t)
        r = np.where(np.abs(t) < 1e-12, np.where(np.abs(e - t) < 1e-9, 0.0, 1.0), r)
        rel[gi] = np.minimum(np.nan_to_num(r, nan=1.0), 1.0)
    return float(rel.mean())


def bf16_rounding(terms: int):
    """The rounding of a bfloat16 matmul of ``terms`` passes against exact
    one-hot weights: 1 keeps the leading bfloat16 of each value; 3 (the
    ``high`` precision) keeps its leading two, the sum hi + lo."""
    import ml_dtypes

    bf16 = ml_dtypes.bfloat16

    def round_(v32: np.ndarray) -> np.ndarray:
        hi = v32.astype(bf16).astype(np.float32)
        if terms == 1:
            return hi
        lo = (v32 - hi).astype(bf16).astype(np.float32)
        return hi.astype(np.float64) + lo.astype(np.float64)

    return round_
