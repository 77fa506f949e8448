"""Draw a configuration's query deck, once; the deck file is then data.

    PYTHONPATH=src python3 perfbench/make_deck.py --config tpch-lineitem --traffic adhoc-deck

The draws are those of the program's workload generator (PS³ §5.1.2;
``repro.queries.generator.WorkloadSpec``), copied here so the deck stays
what it is whatever the program later does: 1–3 aggregates, 0–5 clauses
(some folded into an OR-group), 0–8 group-by columns under the radix cap.
Each numeric constant is recorded as the index of the quantile it was
drawn from, not as its value.  Like the generator, the drawing skips a
query whose text, instantiated on the configuration's table at the deck
seed, repeats an earlier one; nothing else is dropped.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from perfbench.harness import cell as cellmod  # noqa: E402
from perfbench.harness import deck as deckmod  # noqa: E402

MAX_GROUPS = 4096  # the program's cap on a combined group radix


class Drawer:
    """`WorkloadSpec`'s draws, in the same order from the same stream."""

    def __init__(self, schema, rows_per_partition: int, seed: int, n_levels: int):
        self.numeric = [s.name for s in schema if s.kind == "numeric"]
        self.categorical = [s.name for s in schema if s.kind == "categorical"]
        self.groupable = [s.name for s in schema if s.groupable]
        self.card = {s.name: s.cardinality for s in schema}
        self.rng = np.random.default_rng(seed)
        self.max_radix = min(MAX_GROUPS, rows_per_partition)
        self.n_levels = n_levels

    def groupby(self) -> list[str]:
        k = int(self.rng.integers(0, 9))
        if k == 0 or not self.groupable:
            return []
        cols = list(self.rng.permutation(self.groupable))
        chosen, radix = [], 1
        for c in cols[:k]:
            if radix * self.card[c] > self.max_radix:
                continue
            chosen.append(str(c))
            radix *= self.card[c]
        return sorted(chosen)

    def clause(self) -> list:
        rng = self.rng
        if rng.random() < 0.55 and self.numeric:
            col = str(rng.choice(self.numeric))
            op = str(rng.choice(["<", "<=", ">", ">="]))
            return [col, op, {"q": int(rng.choice(self.n_levels))}]
        col = str(rng.choice(self.categorical))
        card = self.card[col]
        if rng.random() < 0.3 and card > 3:
            k = int(rng.integers(2, min(6, card)))
            return [col, "in", [int(v) for v in rng.choice(card, size=k, replace=False)]]
        op = "==" if rng.random() < 0.8 else "!="
        return [col, op, int(rng.integers(0, card))]

    def where(self) -> list:
        k = int(self.rng.integers(0, 6))
        clauses = [self.clause() for _ in range(k)]
        if len(clauses) >= 3 and self.rng.random() < 0.3:
            j = int(self.rng.integers(2, len(clauses) + 1))
            return [clauses[:j]] + [[c] for c in clauses[j:]]
        return [[c] for c in clauses]

    def aggregate(self) -> list:
        r = self.rng.random()
        if r < 0.25:
            return ["count", []]
        kind = "sum" if r < 0.75 else "avg"
        n_terms = 1 if self.rng.random() < 0.7 else 2
        cols = self.rng.choice(self.numeric, size=n_terms, replace=False)
        return [kind, [[float(self.rng.choice([1.0, 1.0, -1.0])), str(c)] for c in cols]]

    def template(self) -> dict:
        n_aggs = int(self.rng.integers(1, 4))
        aggs = [self.aggregate() for _ in range(n_aggs)]
        return {"aggregates": aggs, "where": self.where(), "groupby": self.groupby()}


def draw(cell: cellmod.Cell, size: int, deck_seed: int, levels_spec: list) -> tuple:
    cfg = cell.config
    table = cell.build_table(deck_seed)
    header = {"config": cfg["name"], "deck_seed": deck_seed, "quantile_levels": levels_spec,
              "templates": size}
    levels = deckmod.quantile_levels(header)
    numeric = [s.name for s in table.schema if s.kind == "numeric"]
    quantiles = deckmod.column_quantiles(table.columns, numeric, levels)
    drawer = Drawer(table.schema, cfg["rows_per_partition"], deck_seed, len(levels))
    seen, templates = set(), []
    while len(templates) < size:
        t = drawer.template()
        k = deckmod.key(deckmod.instantiate(t, quantiles))
        if k in seen:
            continue
        seen.add(k)
        templates.append(t)
    return header, templates


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    args = ap.parse_args()
    cell = cellmod.Cell.load(HERE.parent, f"{args.config}.{args.traffic}", check_listed=False)
    tr = cell.traffic
    header, templates = draw(cell, tr["deck_size"], tr["deck_seed"], tr["quantile_levels"])
    out = cell.deck_path
    with open(out, "w") as f:
        for line in [header] + templates:
            f.write(json.dumps(line, separators=(",", ":")) + "\n")
    print(f"{out}: {len(templates)} templates")
    return 0


if __name__ == "__main__":
    sys.exit(main())
