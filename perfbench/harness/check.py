"""Whether a window's answers are correct, by the plain reference.

Two layers are compared, for every answer the window served:

* every chunk read the planner made (the device's per-partition answers
  of those partitions): the group keys and row counts exactly, the sums
  by ``sum_rel_err``, the largest |program - reference| / scale over
  partitions, groups and channels (`reference.Dense.scale`);
* every served estimate against the exact answer over the whole table, by
  PS³'s error: ``met_share`` is the share of the window's answers within
  the bound, held to the share the configuration states.

A control puts the reference, computed at a lower precision, in the
program's place for the chunk reads (`reference.bf16_rounding`).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from perfbench.harness import reference


@dataclasses.dataclass
class Check:
    name: str
    value: float
    op: str  # "<=" | ">="
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit if self.op == "<=" else self.value >= self.limit

    def line(self) -> str:
        return f"check {self.name} {self.value!r} {self.op} {self.limit!r}"


def chunk_difference(ref: reference.Dense, ids: np.ndarray, keys: np.ndarray,
                     raw: np.ndarray) -> tuple[bool, float]:
    """(keys or counts differ, largest scaled sum difference) of one chunk
    read's answers (keys (G,), raw (len(ids), G, 1 + channels))."""
    want = ref.occupied(ids)
    if not np.array_equal(np.asarray(keys), want):
        return True, 0.0
    if raw.shape != (len(ids), want.size, 1 + ref.sums.shape[2]):
        return True, 0.0
    if not np.array_equal(raw[..., 0], ref.count[ids][:, want]):
        return True, 0.0
    if ref.sums.shape[2] == 0 or want.size == 0:
        return False, 0.0
    diff = np.abs(raw[..., 1:] - ref.sums[ids][:, want])
    scale = ref.scale[ids][:, want]
    if np.any(diff[scale == 0] != 0):
        return True, 0.0
    live = scale > 0
    return False, float((diff[live] / scale[live]).max()) if live.any() else 0.0


@dataclasses.dataclass
class Readings:
    unanswered: int = 0
    count_mismatch: int = 0
    sum_rel_err: float = 0.0
    chunks_checked: int = 0
    answers_tested: int = 0
    answers_met: int = 0
    answers_empty: int = 0
    misses: list = dataclasses.field(default_factory=list)  # (deck index, error)

    @property
    def met_share(self) -> float:
        """Answers within the bound over answers with a non-empty exact
        answer; 0 where none was tested, since then nothing was shown."""
        return self.answers_met / self.answers_tested if self.answers_tested else 0.0


def read(record, columns: dict, cardinality: dict, bound: float,
         rounding=None) -> Readings:
    """Compare every answer of ``record`` with the reference.  With
    ``rounding`` the chunk answers compared are the reference's own at
    that rounding (a control), not the program's."""
    out = Readings()
    chunks: dict[str, list] = {}
    for c in record.chunks:
        chunks.setdefault(c.query_text, []).append(c)
    for req in record.requests:
        if req.answer is None:
            out.unanswered += 1
            continue
        ref = reference.dense(columns, cardinality, req.query)
        low = (reference.dense(columns, cardinality, req.query, rounding)
               if rounding is not None else None)
        for c in chunks.get(req.query_text, []):
            if low is not None:
                keys = low.occupied(c.ids)
                raw = np.concatenate(
                    [low.count[c.ids][:, keys, None].astype(np.float64),
                     low.sums[c.ids][:, keys]], axis=-1)
            else:
                keys, raw = c.group_keys, c.raw
            bad, err = chunk_difference(ref, c.ids, keys, raw)
            out.count_mismatch += bad
            out.sum_rel_err = max(out.sum_rel_err, err)
            out.chunks_checked += 1
        truth_keys, truth = ref.exact()
        if truth.size == 0:
            out.answers_empty += 1
            continue
        ans = req.answer
        err = reference.relative_error(truth_keys, truth, ans.group_keys, ans.estimate)
        out.answers_tested += 1
        if err <= bound:
            out.answers_met += 1
        else:
            out.misses.append((req.deck_index, err))
    return out


def checks(r: Readings, limits: dict) -> list[Check]:
    """Each compared number beside its limit (``limits`` from the
    configuration's file)."""
    return [
        Check("unanswered", r.unanswered, "<=", 0),
        Check("count_mismatch", r.count_mismatch, "<=", 0),
        Check("sum_rel_err", r.sum_rel_err, "<=", limits["sum_rel_err"]),
        Check("met_share", r.met_share, ">=", limits["answers_meeting_bound"]),
        Check("chunks_checked", r.chunks_checked, ">=", 1),
    ]
