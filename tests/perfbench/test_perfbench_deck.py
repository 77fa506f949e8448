"""The benchmark's decks: drawing, loading, constants, warm-up variants."""
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import make_deck
from perfbench.harness import cell as cellmod
from perfbench.harness import deck as deckmod

ROOT = Path(__file__).resolve().parents[2]
CELLS = ["tpch-lineitem.adhoc-deck"]


def shape_key(query: dict, cardinality: dict) -> tuple:
    """What sizes a query's eval program: clause intervals (an ``in`` list
    is one per code, ``!=`` two), OR-groups, group radix, sum channels."""
    clauses = sum(
        len(v) if op == "in" else 2 if op == "!=" else 1
        for group in query["where"] for _, op, v in group
    )
    radix = int(np.prod([cardinality[c] for c in query["groupby"]], dtype=np.int64))
    n_raw = 1 + sum(kind != "count" for kind, _ in query["aggregates"])
    return clauses, len(query["where"]), radix, n_raw


@pytest.fixture(scope="module", params=CELLS)
def cell(request):
    return cellmod.Cell.load(ROOT, request.param)


def test_deck_is_its_draw(cell):
    """The committed deck is what `make_deck` draws at its recorded seed."""
    header, templates = deckmod.load(cell.deck_path)
    assert header["config"] == cell.config["name"]
    assert header["deck_seed"] == cell.traffic["deck_seed"]
    assert len(templates) == header["templates"] == cell.traffic["deck_size"] == 1024
    h2, drawn = make_deck.draw(cell, 1024, header["deck_seed"], header["quantile_levels"])
    assert h2 == header
    assert [json.dumps(t) for t in drawn] == [json.dumps(t) for t in templates]


def test_constants_are_the_tables_quantiles(cell):
    header, templates = deckmod.load(cell.deck_path)
    table = cell.build_table(99, 8, 64)
    levels = deckmod.quantile_levels(header)
    numeric = [s.name for s in table.schema if s.kind == "numeric"]
    qs = deckmod.column_quantiles(table.columns, numeric, levels)
    seen = 0
    for tp in templates[:200]:
        inst = deckmod.instantiate(tp, qs)
        for group, igroup in zip(tp["where"], inst["where"]):
            for (col, op, v), (_, _, iv) in zip(group, igroup):
                if isinstance(v, dict):
                    # the generator's 25 levels, 2% to 98%
                    flat = table.columns[col].reshape(-1)
                    want = np.quantile(flat, np.linspace(0.02, 0.98, 25))[v["q"]]
                    assert iv == float(want)
                    assert np.mean(flat <= iv) >= levels[v["q"]] - 1 / flat.size
                    seen += 1
                else:
                    assert iv == v
    assert seen > 50
    # another seed's table moves the values, not the template
    other = deckmod.column_quantiles(cell.build_table(100, 8, 64).columns, numeric, levels)
    a = deckmod.instantiate(templates[0], qs)
    b = deckmod.instantiate(templates[0], other)
    assert a["groupby"] == b["groupby"] and a["aggregates"] == b["aggregates"]


def test_warm_variants_are_new_and_keep_the_shape(cell):
    header, templates = deckmod.load(cell.deck_path)
    table = cell.build_table(5, 8, 64)
    levels = deckmod.quantile_levels(header)
    numeric = [s.name for s in table.schema if s.kind == "numeric"]
    card = {s.name: s.cardinality for s in table.schema}
    qs = deckmod.column_quantiles(table.columns, numeric, levels)
    half = deckmod.column_quantiles(table.columns, numeric, deckmod.midpoint_levels(levels))
    deck = [deckmod.instantiate(tp, qs) for tp in templates]
    taken = {deckmod.key(q) for q in deck}
    for tp, q in zip(templates, deck):
        w = deckmod.warm_variant(tp, half, taken)
        assert deckmod.key(w) not in taken
        assert shape_key(w, card) == shape_key(q, card)
        assert deckmod.to_query(w).describe() != deckmod.to_query(q).describe()


def test_the_table_has_the_domains_its_configuration_states(cell):
    """Each categorical column has the codes its configuration's file says:
    TPC-H's, or the cut that ``reduced`` names."""
    cfg = cell.config
    table = cell.build_table(7, 8, 64)
    card = {s.name: s.cardinality for s in table.schema if s.kind == "categorical"}
    stated = {k: v for k, v in cfg["domains"].items() if isinstance(v, int)}
    cut = {k[: -len("_codes")]: cfg[k] for k in cfg["reduced"] if k.endswith("_codes")}
    assert card == {**stated, **cut}
    assert all(f"{name}_codes" in cfg["why_reduced"] for name in cut)
    for name, codes in card.items():
        assert table.columns[name].min() >= 0 and table.columns[name].max() < codes
