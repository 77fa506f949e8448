"""The program's own spans and read-cost counters, and the reduction of the
spans to host time per phase and to the chip's idle time (`harness/spans.py`):
on a traced CPU run of the served path, and on hand-built event lists."""
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import repro.api as api
import repro.planner.planner as planner_mod
from perfbench.harness import cell as cellmod
from perfbench.harness import serve, spans, trace
from repro.backends import ExecOptions
from repro.core.picker import PickerConfig
from repro.data.datasets import make_dataset
from repro.queries.generator import WorkloadSpec
from repro.serving import FrontDoor

ROOT = Path(__file__).resolve().parents[2]
# the jitted eval path, so that the chunk reads build and upload stacks
JITTED = ExecOptions(backend="device", mesh=None, use_ref=True)
PARENTS = {
    "door.flush": {None},
    "planner.select": {"door.flush"},
    "planner.read": {"planner.select", "door.flush"},  # outliers; rounds
    "planner.estimate": {"door.flush"},
    "planner.merge": {"planner.read"},
    "read.gather": {"planner.read"},
    "eval.prepare": {"planner.read"},
    "eval.upload": {"planner.read"},
    "eval.unpack": {"planner.read"},
}
METRICS = ("planner.select_s_per_answer", "planner.merge_s_per_answer",
           "planner.estimate_s_per_answer", "read.host_s_per_answer",
           "device.idle_in_planner_share", "device.idle_in_read_share",
           "planner.merge_bytes_per_answer", "read.upload_bytes_per_answer")


def reader(name):
    return cellmod.load_module(ROOT / "perfbench" / "metrics" / f"{name}.py", f"s_{name}")


@pytest.fixture(scope="module")
def served():
    """Four answers through a front door, traced, with every chunk read the
    planner made recorded beside its answer, and the bytes of every
    ``(keys, raw)`` that `_merge_raw` returned, one entry per call."""
    table = make_dataset("tpch", num_partitions=48, rows_per_partition=64)
    sess = api.Session(table, options=JITTED)
    sess.prepare(WorkloadSpec(table, seed=1), num_train_queries=8,
                 picker_config=PickerConfig(num_trees=4, tree_depth=3,
                                            feature_selection=False))
    reads = []
    get_subset = sess.answers.get_subset

    def recorded(query, part_ids):
        ans = get_subset(query, part_ids)
        reads.append((query.describe(), np.array(part_ids), ans.group_keys, ans.raw.shape))
        return ans

    sess.answers.get_subset = recorded
    merges = []
    merge_raw = planner_mod._merge_raw

    def recorded_merge(*args):
        keys, raw = merge_raw(*args)
        merges.append(keys.nbytes + raw.nbytes)
        return keys, raw

    queries = [q for q in WorkloadSpec(table, seed=7).sample_workload(12) if q.groupby][:4]
    events = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(planner_mod, "_merge_raw", recorded_merge)
        door = FrontDoor(sess).start()
        try:
            with trace.capture(events):
                with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                    answers = [door.submit(api.QuerySpec(q, error_bound=0.05)).result(timeout=600)
                               for q in queries]
        finally:
            door.stop()
    return sess, answers, reads, merges, events


def _parents(events):
    """Each program span's enclosing program span, found independently of
    `spans`: the shortest program span on the same line that holds it."""
    prog = [e for e in events if e["name"] in spans.PROGRAM]
    out = []
    for e in prog:
        a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
        holders = [p for p in prog if p is not e and p["line"] == e["line"]
                   and p["start_ns"] <= a and b <= p["start_ns"] + p["dur_ns"]]
        parent = min(holders, key=lambda p: p["dur_ns"])["name"] if holders else None
        out.append((e["name"], parent))
    return out


def test_every_span_sits_under_its_parent_on_one_line(served):
    sess, answers, _, merge_calls, events = served
    prog = [e for e in events if e["name"] in spans.PROGRAM]
    assert len({(e["plane"], e["line"]) for e in prog}) == 1
    pairs = _parents(events)
    assert {name for name, _ in pairs} == set(spans.PROGRAM)
    for name, parent in pairs:
        assert parent in PARENTS[name], (name, parent)
    flushes = sum(1 for e in prog if e["name"] == "door.flush")
    assert flushes == len(answers)
    # one span per `_merge_raw` call, however many chunk reads each folds
    merges = sum(1 for e in prog if e["name"] == "planner.merge")
    chunk_reads = sum(a.plan.chunk_reads for a in answers)
    assert merges == len(merge_calls)
    assert len(answers) <= merges <= chunk_reads == sess.stats()["chunk_evals"]


def test_merge_bytes_are_the_shapes_merge_allocates(served):
    """The counter adds up the arrays `_merge_raw` returned, each once,
    whatever the schedule of merges.  Per answer that lies between its
    final merged array alone, (partitions read, groups seen, channels)
    float64 beside the int64 group keys, and a new such array at every
    chunk read."""
    sess, answers, reads, merges, _ = served
    assert sum(a.plan.merge_bytes for a in answers) == sum(merges) == sess.stats()["merge_bytes"]
    k = 0
    for ans in answers:
        text = ans.query.describe()
        rows, keys, channels, per_read = 0, np.empty(0, np.int64), 0, 0
        for _ in range(ans.plan.chunk_reads):
            q, ids, group_keys, shape = reads[k]
            k += 1
            assert q == text
            rows += np.unique(ids).size  # a short chunk pads with its first id
            keys = np.union1d(keys, group_keys)
            channels = shape[2]
            per_read += 8 * keys.size + 8 * rows * keys.size * channels
        final = 8 * keys.size + 8 * rows * keys.size * channels
        assert 0 < final <= ans.plan.merge_bytes <= per_read
        assert rows == ans.partitions_read
    assert k == len(reads)


def test_upload_bytes_per_answer_add_up_to_the_session_total(served):
    sess, answers, reads, _, _ = served
    total = sess.stats()["upload_bytes"]
    assert sum(a.plan.upload_bytes for a in answers) == total
    # every chunk read missed and uploaded a fresh (columns + 1, 16, rows)
    # float32 stack, then a few hundred bytes of descriptors
    stack = (len(sess.table.schema) + 1) * 16 * sess.table.rows_per_partition * 4
    assert len(reads) * stack < total < len(reads) * (stack + 4096)
    assert sess.stats()["merge_bytes"] == sum(a.plan.merge_bytes for a in answers)


def test_the_traced_run_feeds_the_new_readers(served):
    _, answers, _, _, events = served
    rec = serve.Record(seconds=1.0, start=0.0, events=events)
    rec.requests = [serve.Request(i, {}, "", 0.0, 0.1, a) for i, a in enumerate(answers)]
    got = {name: reader(name).read(rec) for name in METRICS}
    # no chip, so no idle time to share out
    assert got.pop("device.idle_in_planner_share") is None
    assert got.pop("device.idle_in_read_share") is None
    assert all(v is not None and v > 0 for v in got.values()), got


# --------------------------------------------------------------------------
# the reduction, on hand-built events (ns)
# --------------------------------------------------------------------------
def ev(name, a, b, line="python3", plane="/host:CPU"):
    return {"plane": plane, "line": line, "name": name, "start_ns": float(a),
            "dur_ns": float(b - a)}


def busy(a, b):
    return ev("fused_eval", a, b, line="XLA Ops", plane="/device:TPU:0")


# one flush thread; the harness's spans on the same line name, one of them
# partly overlapping; a second thread with a program span of its own; the
# chip idle across many span boundaries
EVENTS = [
    ev(trace.WINDOW_SPAN, 0, 1000), ev("client.wait", 50, 950), ev("serve.execute", 100, 890),
    ev("client.wait", 900, 990), ev("$threading.py:1 run", 0, 1000),
    ev("door.flush", 100, 900),
    ev("planner.select", 100, 300),
    ev("planner.read", 200, 280),
    ev("read.gather", 200, 220), ev("eval.prepare", 220, 230),
    ev("PjitFunction(_eval_core)", 230, 240), ev("np.asarray(jax.Array)", 240, 270),
    ev("eval.unpack", 270, 275), ev("planner.merge", 275, 280),
    ev("planner.estimate", 300, 350),
    ev("planner.read", 350, 450),
    ev("read.gather", 350, 380), ev("eval.upload", 380, 400), ev("DevicePut", 390, 400),
    ev("PjitFunction(_eval_core)", 400, 410), ev("np.asarray(jax.Array)", 410, 440),
    ev("planner.merge", 440, 450),
    ev("planner.estimate", 450, 500),
    ev("door.flush", 980, 1020), ev("planner.estimate", 980, 1020),  # past the close
    ev("planner.select", 600, 700, line="worker"),
    busy(0, 100), busy(210, 215), busy(245, 265), busy(415, 435), busy(460, 470),
    busy(950, 1000),
]


def test_self_time_subtracts_inner_program_spans_only():
    got = {k: round(v * 1e9, 6) for k, v in spans.self_seconds(EVENTS).items()}
    assert got == {
        "door.flush": 400, "planner.select": 120 + 100, "planner.read": 40 + 40,
        "read.gather": 50, "eval.prepare": 10, "eval.upload": 20, "eval.unpack": 5,
        "planner.merge": 15, "planner.estimate": 50 + 50 + 20,
    }
    assert spans.seconds_in(EVENTS, "planner.merge") * 1e9 == pytest.approx(15)
    assert spans.seconds_in(EVENTS, "planner.estimate") * 1e9 == pytest.approx(120)
    # the read path's own host time: its spans, the launches, not the fetches
    assert spans.read_host_seconds(EVENTS) * 1e9 == pytest.approx(50 + 10 + 20 + 5 + 10 + 10)


def test_idle_time_goes_to_the_flush_threads_innermost_span_instant_by_instant():
    """Idle 795 ns: planner 225 (its select, estimates and merges), read 120
    (gathers, descriptors, upload, launches, fetches, unpack), the flush's
    own 400, and 50 after it; the second thread's span takes none."""
    got = spans.idle_shares(EVENTS)
    assert got == pytest.approx({"planner": 100 * 225 / 795, "read": 100 * 120 / 795,
                                 "door": 100 * 400 / 795})
    rec = serve.Record(seconds=1.0, start=0.0, events=EVENTS)
    assert reader("device.idle_in_planner_share").read(rec) == pytest.approx(100 * 225 / 795)
    assert reader("device.idle_in_read_share").read(rec) == pytest.approx(100 * 120 / 795)
    # without the chip's trace there is nothing to share out
    assert spans.idle_shares([e for e in EVENTS if e["plane"] != "/device:TPU:0"]) is None


# one idle gap of the chip, [400, 600), and the host spans around it
GAP_CASES = {
    "planner.merge": [ev("client.wait", 50, 950), ev("serve.execute", 100, 900),
                      ev("door.flush", 90, 910), ev("planner.read", 300, 700),
                      ev("planner.merge", 450, 550)],
    # JAX's own events, shorter still, are not among the names
    "read.gather": [ev("serve.execute", 100, 900), ev("planner.read", 300, 700),
                    ev("serve.chunk_read", 350, 650), ev("read.gather", 400, 600),
                    ev("np.asarray(jax.Array)", 480, 520)],
    # program spans before and after the gap, none open at its middle
    "serve.execute": [ev("client.wait", 50, 950), ev("serve.execute", 100, 900),
                      ev("planner.select", 100, 450), ev("planner.estimate", 550, 900)],
    # the window's own span, open across every gap of a real trace, kept off
    # the host's lines: nothing is open
    "no span open": [ev("planner.merge", 100, 300)],
}


@pytest.mark.parametrize("name", GAP_CASES)
def test_an_idle_gap_is_named_by_the_innermost_span_open_at_its_middle(name):
    window = ev(trace.WINDOW_SPAN, 0, 1000,
                plane="/host:CPU" if name != "no span open" else "/device:TPU:0")
    events = [window, busy(0, 400), busy(600, 1000)] + GAP_CASES[name]
    assert trace.idle_gaps(events) == [[name, pytest.approx(200e-9)]]


def test_new_readers_leave_out_what_the_parent_program_lacks():
    """The parent has no program spans and its plans no byte counts."""
    harness_only = [e for e in EVENTS if e["name"] not in spans.PROGRAM]
    plan = type("Plan", (), {"rounds": 1})()
    answer = type("Answer", (), {"plan": plan, "partitions_read": 16})()
    for events in (None, harness_only):
        rec = serve.Record(seconds=1.0, start=0.0, events=events)
        rec.requests = [serve.Request(0, {}, "", 0.0, 0.1, answer)]
        assert {name: reader(name).read(rec) for name in METRICS} == dict.fromkeys(METRICS)


def test_phases_splits_each_request_and_covers_the_execute_idle():
    """`phases.py` on the hand-built window: two flushes, two requests."""
    phases = cellmod.load_module(ROOT / "perfbench" / "phases.py", "perfbench_phases")
    plan = SimpleNamespace(chunk_reads=2, merge_bytes=64, upload_bytes=128)
    answer = SimpleNamespace(plan=plan, partitions_read=16)
    rec = serve.Record(seconds=1.0, start=0.0, events=EVENTS)
    rec.requests = [serve.Request(i, {}, "", 0.0, 0.1, answer) for i in (3, 4)]
    got = phases.breakdown(rec)
    first, last = got["requests"]
    assert first["deck_index"] == 3 and first["chunk_reads"] == 2
    assert {k: round(v * 1e9, 6) for k, v in first["self_s"].items()} == {
        "door.flush": 400, "planner.select": 120, "planner.read": 80, "read.gather": 50,
        "eval.prepare": 10, "eval.upload": 20, "eval.unpack": 5, "planner.merge": 15,
        "planner.estimate": 100}
    assert {k: round(v * 1e9, 6) for k, v in first["idle_s"].items()} == {
        "planner": 225, "read": 120, "door": 400}
    assert {k: round(v * 1e9, 6) for k, v in last["self_s"].items()} == {"planner.estimate": 20}
    # idle inside serve.execute [100, 890]: 110 + 30 + 150 + 25 + 420
    assert got["idle_in_serve_execute_s"] * 1e9 == pytest.approx(735)
    assert got["planner_and_read_share_of_it"] == pytest.approx(100 * 345 / 735)
    assert got["program_spans_per_answer"] == 16 / 2
