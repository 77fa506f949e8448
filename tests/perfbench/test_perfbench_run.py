"""Whole runs of the harness on CPU at a small size: the result line, the
deck's rules, and `correct` coming out false when the timed path is broken
underneath it or the control takes the program's place."""
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from perfbench import control
from perfbench.harness import cell as cellmod
from perfbench.harness import check
from perfbench.harness import deck as deckmod
from perfbench.harness import runner
from repro.api import Session
from repro.core.picker import PickerConfig
from repro.queries.engine import AnswerStore, PartitionAnswers

ROOT = Path(__file__).resolve().parents[2]
CELL = "tpch-lineitem.adhoc-deck"
SMALL = dict(
    num_partitions=32, rows_per_partition=2048,
    prepare_kw={"num_train_queries": 8,
                "picker_config": PickerConfig(num_trees=4, tree_depth=3,
                                              feature_selection=False)},
)
SEED = 2**31 + 12345  # past 32 signed bits, as the driver's seeds are


def quiet(*_):
    pass


def small_run(cell, seconds=1.0, traced=False):
    return runner.run(cell, SEED, seconds, traced, log=quiet, **SMALL)


@pytest.fixture(scope="module")
def cell():
    return cellmod.Cell.load(ROOT, CELL)


def test_a_sound_run_is_correct_and_reports_its_metrics(cell):
    result, checks = small_run(cell)
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert result["failed"] == 0 and result["attempted"] > 5
    assert set(result["metrics"]) == {"latency_p75_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert {c.name for c in checks} == set(result["checks"])


def test_a_traced_run_reports_per_layer_metrics(cell):
    result, _ = small_run(cell, traced=True)
    assert result["correct"] is True
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    # no chip, so nothing the device trace would give is reported
    assert "latency_p75_s" not in result["metrics"]
    assert {"planner.rounds_per_answer", "setup.picker_train_s",
            "jit.compiles_in_window"} <= set(result["metrics"])
    assert not {"device.idle_share", "kernel.eval_roofline"} & set(result["metrics"])


def _altered(orig):
    def get_subset(self, query, part_ids):
        ans = orig(self, query, part_ids)
        raw = ans.raw.copy()
        raw[..., 1:] *= 1 + 1e-4
        return PartitionAnswers(ans.query, ans.group_keys, raw, ans.plans)
    return get_subset


def _half_left_out(orig):
    def get_subset(self, query, part_ids):
        ans = orig(self, query, part_ids)
        raw = ans.raw.copy()
        half = raw.shape[0] // 2
        raw[half:] = raw[:half].mean(axis=0)  # the rest's mean stands in
        return PartitionAnswers(ans.query, ans.group_keys, raw, ans.plans)
    return get_subset


def _stale(orig):
    last = {}

    def execute(self, spec, **kw):
        ans = orig(self, spec, **kw)
        prev = last.get(id(self))
        last[id(self)] = ans
        return prev if prev is not None else ans
    return execute


@pytest.mark.parametrize("fault, where, fails", [
    ("answer altered where produced", (AnswerStore, "get_subset", _altered), "sum_rel_err"),
    ("half of each chunk left out, the rest's mean in its place",
     (AnswerStore, "get_subset", _half_left_out),
     "count_mismatch"),
    ("the previous answer served again", (Session, "execute", _stale),
     "met_share"),
])
def test_a_broken_timed_path_is_not_correct(cell, monkeypatch, fault, where, fails):
    owner, name, make = where
    monkeypatch.setattr(owner, name, make(getattr(owner, name)))
    result, checks = small_run(cell)
    assert result["correct"] is False, fault
    assert fails in {c.name for c in checks if not c.ok}, result["checks"]


def test_the_control_fails_the_sum_limit(cell):
    r = control.readings(cell, SEED, 1.0, log=quiet, **SMALL)
    limit = cell.config["limits"]["sum_rel_err"]
    assert r["program"].sum_rel_err <= limit and r["program"].count_mismatch == 0
    for name in control.CONTROLS:
        assert r[name].sum_rel_err > limit, (name, r[name].sum_rel_err)
    assert r["high"].sum_rel_err < r["bfloat16"].sum_rel_err


def four_template_cell(root, cell, **traffic):
    """The cell with a deck of four templates, the first two equal, and its
    traffic file changed as ``traffic`` says, as files under ``root``."""
    bench = root / "perfbench"
    (bench / "traffic" / "decks").mkdir(parents=True)
    shutil.copytree(ROOT / "perfbench" / "configs", bench / "configs")
    shutil.copy(ROOT / "perfbench" / "peaks.json", bench / "peaks.json")
    (bench / "traffic" / "adhoc-deck.json").write_text(json.dumps({**cell.traffic, **traffic}))
    header, templates = deckmod.load(cell.deck_path)
    lines = [header] + [templates[0]] + templates[:3]
    (bench / "traffic" / "decks" / cell.deck_path.name).write_text(
        "".join(json.dumps(x) + "\n" for x in lines))
    return cellmod.Cell.load(root, CELL, check_listed=False)


@pytest.fixture
def small_deck_cell(tmp_path, cell):
    return four_template_cell(tmp_path, cell)


def test_a_window_past_the_deck_fails_and_never_repeats(small_deck_cell, monkeypatch):
    served = []
    orig = runner.serve.window

    def window(*a, **kw):
        try:
            return orig(*a, **kw)
        finally:
            served.extend(a[1].probe.chunks)

    monkeypatch.setattr(runner.serve, "window", window)
    with pytest.raises(deckmod.DeckExhausted, match="never wraps"):
        runner.run(small_deck_cell, SEED, 60.0, False, log=quiet, **SMALL)
    texts = {c.query_text for c in served}
    assert len(texts) == 3  # four templates, the repeat skipped


def test_deck_order_is_file_order(cell):
    m = runner.measure(cell, SEED, 0.5, False, log=quiet, **SMALL)
    idx = [r.deck_index for r in m.record.requests]
    assert idx == list(range(len(idx))) and len(idx) >= 2
    assert len({r.query_text for r in m.record.requests}) == len(idx)
    assert np.all(np.diff([r.submitted for r in m.record.requests]) > 0)


def test_clients_and_a_cycling_deck_come_from_the_traffic_file(tmp_path, cell):
    """Two clients in a closed loop on a deck that cycles: requests overlap,
    the deck's queries repeat past its end, and the run stays correct."""
    two = four_template_cell(tmp_path, cell, clients=2, deck_order="cycle")
    m = runner.measure(two, SEED, 1.5, False, log=quiet, **SMALL)
    reqs = m.record.requests
    assert len(reqs) > 4 and all(r.answer is not None for r in reqs)
    assert len({r.query_text for r in reqs}) == 3 < len(reqs)  # the deck's three texts, again
    assert any(b.submitted < a.done for a, b in zip(reqs, reqs[1:]))  # two in flight at once
    readings = check.read(m.record, m.columns, m.cardinality, two.traffic["error_bound"])
    assert all(c.ok for c in check.checks(readings, two.config["limits"]))


def test_think_time_spaces_a_clients_requests(tmp_path, cell):
    slow = four_template_cell(tmp_path, cell, think_s=0.3, deck_order="cycle")
    m = runner.measure(slow, SEED, 2.0, False, log=quiet, **SMALL)
    reqs = m.record.requests
    assert 2 <= len(reqs) <= 7
    assert all(b.submitted - a.done >= 0.3 for a, b in zip(reqs, reqs[1:]))


@pytest.mark.parametrize("traffic", [{"clients": 0}, {"think_s": -1.0},
                                     {"deck_order": "shuffled"}])
def test_a_traffic_file_out_of_range_is_refused(tmp_path, cell, traffic):
    bad = four_template_cell(tmp_path, cell, **traffic)
    with pytest.raises(ValueError, match="deck_order"):
        runner.run(bad, SEED, 0.5, False, log=quiet, **SMALL)


def test_a_stale_door_fails_the_met_share(cell):
    """`control.stale` serves each answer one request late; its met share
    falls under the limit where the program's meets it."""
    m = runner.measure(cell, SEED, 1.0, False, log=quiet, **SMALL)
    limit = cell.config["limits"]["answers_meeting_bound"]
    bound = cell.traffic["error_bound"]
    sound = check.read(m.record, m.columns, m.cardinality, bound)
    late = check.read(control.stale(m.record), m.columns, m.cardinality, bound)
    assert sound.met_share >= limit > late.met_share
    assert late.answers_tested >= 5 and late.chunks_checked == 0
    assert control.fails(late, cell.config["limits"]) and not control.fails(
        sound, cell.config["limits"])
