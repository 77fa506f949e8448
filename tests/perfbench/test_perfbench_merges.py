"""`planner.chunk_reads_per_merge`: the reader of `QueryPlan.merges`, on
answers the program served and on plans without the counter."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import repro.api as api
from perfbench.harness import cell as cellmod
from perfbench.harness import serve
from repro.backends import ExecOptions
from repro.core.picker import PickerConfig
from repro.data.datasets import make_dataset
from repro.planner import PlannerConfig
from repro.queries.generator import WorkloadSpec

ROOT = Path(__file__).resolve().parents[2]
NAME = "planner.chunk_reads_per_merge"


def reader():
    return cellmod.load_module(ROOT / "perfbench" / "metrics" / f"{NAME}.py", "m_merges")


def record(answers):
    rec = serve.Record(seconds=1.0, start=0.0)
    rec.requests = [serve.Request(i, {}, "", 0.0, 0.1, a) for i, a in enumerate(answers)]
    return rec


def answer(**plan):
    return SimpleNamespace(plan=SimpleNamespace(**plan), partitions_read=16)


@pytest.fixture(scope="module")
def answers():
    """Error-bounded answers of a session that reads four partitions per
    chunk, so that a round's `_read` call folds several chunk reads."""
    table = make_dataset("tpch", num_partitions=48, rows_per_partition=64)
    sess = api.Session(table, options=ExecOptions(backend="host"),
                       planner_config=PlannerConfig(chunk=4))
    sess.prepare(WorkloadSpec(table, seed=1), num_train_queries=8,
                 picker_config=PickerConfig(num_trees=4, tree_depth=3,
                                            feature_selection=False))
    queries = [q for q in WorkloadSpec(table, seed=7).sample_workload(12) if q.groupby][:4]
    return [sess.execute(api.QuerySpec(q, error_bound=0.05)) for q in queries]


def test_the_reader_takes_the_mean_of_chunk_reads_per_merge(answers):
    plans = [a.plan for a in answers]
    assert all(0 < p.merges <= p.chunk_reads for p in plans)
    want = sum(p.chunk_reads / p.merges for p in plans) / len(plans)
    assert reader().read(record(answers)) == pytest.approx(want)
    assert want > 1  # several chunk reads per fold


def test_answers_that_read_nothing_are_left_out():
    folded = answer(chunk_reads=6, merges=2)
    view_hit = answer(chunk_reads=0, merges=0)
    assert reader().read(record([folded, view_hit, None])) == 3.0
    assert reader().read(record([view_hit])) is None
    assert reader().read(record([])) is None


def test_the_reader_leaves_out_what_the_parent_program_lacks():
    """The parent's plans count chunk reads but not merges."""
    parent = answer(chunk_reads=6, merge_bytes=64, upload_bytes=128)
    assert reader().read(record([parent, answer(chunk_reads=6, merges=2)])) is None
