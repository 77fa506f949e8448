"""Query decks: fixed templates whose constants come from each seed's table.

A deck is a JSON-lines file.  Its first line is a header (the
configuration, the seed it was drawn at and the quantile levels); every
further line is one template:

    {"aggregates": [["sum", [[1.0, "l_quantity"]]], ["count", []]],
     "where": [[["l_shipdate", "<", {"q": 12}]], [["p_brand", "==", 3]]],
     "groupby": ["l_returnflag"]}

``where`` is a conjunction of OR-groups of clauses.  A numeric constant is
``{"q": i}``: the i-th of the table's quantiles at the header's levels.  A
categorical constant is a code, or a list of codes for ``in``.  So a
template fixes the shape of its query and nearly its selectivity, and the
seed picks only the data underneath.

A template instantiated for a table is a plain dict of the same shape with
every ``{"q": i}`` replaced by its float; `to_query` turns that into the
program's query IR and `reference` evaluates it without the program.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np


class DeckExhausted(RuntimeError):
    """The window asked for more templates than the deck holds."""


def load(path: Path) -> tuple[dict, list[dict]]:
    """→ (header, templates) of one deck file."""
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    if not lines or "deck_seed" not in lines[0]:
        raise ValueError(f"{path}: first line is not a deck header")
    return lines[0], lines[1:]


def quantile_levels(header: dict) -> np.ndarray:
    lo, hi, n = header["quantile_levels"]
    return np.linspace(lo, hi, int(n))


def column_quantiles(columns: dict, numeric: list[str], levels: np.ndarray) -> dict:
    """Per numeric column, its quantiles at ``levels`` over the whole table."""
    return {c: np.quantile(np.asarray(columns[c]).reshape(-1), levels) for c in numeric}


def instantiate(template: dict, quantiles: dict) -> dict:
    """The template with each ``{"q": i}`` replaced by that quantile."""

    def value(col, v):
        if isinstance(v, dict):
            return float(quantiles[col][v["q"]])
        return v

    return {
        "aggregates": template["aggregates"],
        "where": [[[c, op, value(c, v)] for c, op, v in group] for group in template["where"]],
        "groupby": template["groupby"],
    }


def key(query: dict) -> str:
    """Canonical text of an instantiated query: equal keys, equal queries."""
    return json.dumps(query, sort_keys=True)


def to_query(query: dict):
    """The program's IR for an instantiated template."""
    from repro.queries.ir import Aggregate, Clause, OrGroup, Predicate, Query

    aggs = tuple(
        Aggregate(kind, tuple((float(w), c) for w, c in terms))
        for kind, terms in query["aggregates"]
    )
    groups = tuple(
        OrGroup(tuple(
            Clause(c, op, tuple(int(x) for x in v) if op == "in" else v)
            for c, op, v in group
        ))
        for group in query["where"]
    )
    return Query(aggs, Predicate(groups), tuple(query["groupby"]))


def warm_variant(template: dict, half_quantiles: dict, taken: set[str]) -> dict:
    """A query of the template's shape that no template of the deck asks.

    ``half_quantiles`` holds, per numeric column, the quantiles halfway
    between two neighbouring levels of the deck (`midpoint_levels`), and a
    numeric constant ``{"q": i}`` takes the i-th of them.  One COUNT(*)
    more, which adds no aggregate channel, is appended until the key is
    not among ``taken``.
    """

    def value(col, v):
        if isinstance(v, dict):
            return float(half_quantiles[col][min(v["q"], len(half_quantiles[col]) - 1)])
        return v

    q = {
        "aggregates": list(template["aggregates"]),
        "where": [[[c, op, value(c, v)] for c, op, v in group] for group in template["where"]],
        "groupby": template["groupby"],
    }
    while True:
        q["aggregates"] = q["aggregates"] + [["count", []]]
        if key(q) not in taken:
            return q


def midpoint_levels(levels: np.ndarray) -> np.ndarray:
    return (levels[:-1] + levels[1:]) / 2
