"""The metric readers and the trace reduction, on made-up records and on a
recorded slice of a chip trace (one v5e, 16,384-row partitions, 4.28 s)."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench.harness import cell as cellmod
from perfbench.harness import serve, trace

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_slice.json.gz"


def reader(name):
    return cellmod.load_module(ROOT / "perfbench" / "metrics" / f"{name}.py", f"m_{name}")


def request(i, t0, lat, answered=True, query=None):
    ans = object() if answered else None
    return serve.Request(i, query or {}, f"q{i}", t0, t0 + lat, ans)


@pytest.fixture(scope="module")
def events():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def test_p75_is_over_every_request_of_the_window():
    rec = serve.Record(seconds=10.0, start=100.0)
    lats = [0.1 * (i + 1) for i in range(8)]
    t = 100.0
    for i, lat in enumerate(lats):
        rec.requests.append(request(i, t, lat))
        t += lat
    # the request in flight at the close counts in the tail
    rec.requests.append(request(8, 109.9, 5.0))
    got = reader("latency_p75_s").read(rec)
    assert got == pytest.approx(np.percentile(lats + [5.0], 75))
    assert got > np.percentile(lats, 75)


def _sweep_busy(events, plane, lo, hi):
    """Busy time by a sweep over interval ends (a second way of counting)."""
    edges = []
    for e in events:
        if e["plane"] == plane and e["line"] in trace.BUSY_LINES:
            a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
            if b > a:
                edges += [(a, 1), (b, -1)]
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(edges):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy / 1e9


def test_idle_share_and_eval_time_from_a_recorded_trace(events):
    lo, hi = trace.window(events)
    busy, window_s = trace.busy_seconds(events)
    assert window_s == pytest.approx((hi - lo) / 1e9)
    assert busy == pytest.approx(_sweep_busy(events, "/device:TPU:0", lo, hi), rel=1e-12)
    rec = serve.Record(seconds=window_s, start=0.0, events=events)
    rec.requests = [request(i, 0.0, 0.1) for i in range(3)]
    idle = reader("device.idle_share").read(rec)
    assert idle == pytest.approx(100 * (1 - busy / window_s))
    assert 50 < idle < 100
    evals = [e for e in events if e["line"] == "XLA Modules"
             and e["name"].startswith(("jit__eval_core(", "jit__eval_nopred_core("))]
    assert evals
    per_answer = reader("eval.device_s_per_answer").read(rec)
    assert per_answer == pytest.approx(sum(e["dur_ns"] for e in evals) / 1e9 / 3)


def test_breakdown_names_ops_and_gaps(events):
    ops = trace.device_ops(events)
    assert ops[0][0] == "fused_eval" and len(ops) <= trace.TOP
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = trace.idle_gaps(events)
    assert gaps and {n for n, _ in gaps} <= set(trace.SPANS) | {"no span open"}
    assert gaps[0][1] >= gaps[-1][1]


def test_roofline_counts_needed_bytes_and_stays_under_100(events):
    """Each eval launch of the slice read one 16-partition chunk of 16,384-row
    partitions; even with every column of the table counted, the bytes
    those chunk reads need take under 100% of the launches' time."""
    roofline = reader("kernel.eval_roofline")
    n = sum(1 for e in events if e["line"] == "XLA Modules"
            and e["name"].startswith(("jit__eval_core(", "jit__eval_nopred_core(")))
    cols = [f"c{i}" for i in range(16)]
    query = {"aggregates": [["sum", [[1.0, c]]] for c in cols[:3]],
             "where": [[[c, "<", 1.0]] for c in cols[3:8]], "groupby": cols[8:]}
    rec = serve.Record(seconds=1.0, start=0.0, events=events, device_kind="TPU v5 lite",
                       rows_per_partition=16384,
                       peaks=json.loads((ROOT / "perfbench" / "peaks.json").read_text()))
    rec.requests = [request(0, 0.0, 0.1, query=query)]
    rec.chunks = [serve.ChunkRead("q0", np.arange(16), np.arange(7), np.zeros((16, 7, 4)))
                  for _ in range(n)]
    need = roofline.needed_bytes(rec)
    assert need == n * (16 * 16384 * 16 * 4 + 16 * 7 * 4 * 4)
    share = roofline.read(rec)
    assert 0 < share < 100
    # padding the program adds (a chunk padded by repeating one partition)
    # is not needed work
    rec.chunks = [serve.ChunkRead("q0", np.array([3] * 16), np.arange(7), np.zeros((16, 7, 4)))]
    assert roofline.needed_bytes(rec) == 16384 * 16 * 4 + 7 * 4 * 4
    # a device the peak table lacks is an error, not a default
    rec.device_kind = "TPU v99"
    with pytest.raises(KeyError):
        roofline.read(rec)


def test_readers_leave_out_what_they_cannot_read():
    rec = serve.Record(seconds=1.0, start=0.0)
    for name in ("device.idle_share", "eval.device_s_per_answer", "kernel.eval_roofline"):
        assert reader(name).read(rec) is None
    assert reader("planner.partitions_read_per_answer").read(rec) is None


def test_every_listed_metric_has_a_reader():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = cellmod.Cell.load(ROOT, w["name"])
        names = {m.name for m in cell.metrics}
        assert {"setup_s", "latency_p75_s"} <= names
        assert {m["name"] for m in spec["per_layer"]} <= names
