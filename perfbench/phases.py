"""One traced window of a cell, with the host time of each request split by
the program's spans and the chip's idle time split by layer.

    python3 perfbench/phases.py --workload tpch-lineitem.adhoc-deck --seed 7 --seconds 51

From the root of a checkout, on the chip, for a one-client traffic: the
front door then flushes each request alone, so the window's k-th
``door.flush`` span is its k-th request.  The last line of stdout is JSON:
per request its deck index, latency, counters, the self time of each
program span and the device-idle seconds under each layer (`harness/spans.py`);
for the window, the device-idle seconds inside the harness's
``serve.execute`` spans and the share of them the planner and read layers
account for, and the program spans opened per answer.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _overlap(intervals, a, b) -> float:
    return sum(max(0.0, min(b, v) - max(a, u)) for u, v in intervals)


def breakdown(record) -> dict:
    from perfbench.harness import spans, trace

    events = record.events
    lo, hi = trace.window(events)
    busy = trace.busy_intervals(events, trace.device_planes(events)[0], lo, hi)
    idle, t = [], lo
    for a, b in busy + [[hi, hi]]:
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    flushes = sorted(e["start_ns"] for e in events if e["name"] == "door.flush")
    if len(flushes) != len(record.requests):
        raise ValueError(f"{len(flushes)} door.flush spans for {len(record.requests)} "
                         "requests; phases needs one client")
    rows = [{"deck_index": r.deck_index, "latency_s": r.latency,
             "partitions_read": r.answer.partitions_read if r.answer else None,
             "chunk_reads": r.answer.plan.chunk_reads if r.answer else None,
             "merge_bytes": r.answer.plan.merge_bytes if r.answer else None,
             "upload_bytes": r.answer.plan.upload_bytes if r.answer else None,
             "self_s": {}, "idle_s": {}} for r in record.requests]
    k = -1
    for a, b, path in sorted(s for s in spans.stretches(events) if s[2][0] == "door.flush"):
        while k + 1 < len(flushes) and flushes[k + 1] <= a:
            k += 1
        row = rows[k]
        span, layer = spans.program_span(path), spans.layer(path)
        row["self_s"][span] = row["self_s"].get(span, 0.0) + (b - a) / 1e9
        row["idle_s"][layer] = row["idle_s"].get(layer, 0.0) + _overlap(idle, a, b) / 1e9
    execute = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
               if e["name"] == "serve.execute"]
    in_execute = sum(_overlap(idle, max(a, lo), min(b, hi)) for a, b in execute) / 1e9
    covered = sum(r["idle_s"].get(k, 0.0) for r in rows for k in ("planner", "read"))
    opened = sum(1 for e in events if e["name"] in spans.PROGRAM
                 and lo <= e["start_ns"] < hi)
    return {"requests": rows, "idle_in_serve_execute_s": in_execute,
            "planner_and_read_share_of_it": 100.0 * covered / in_execute,
            "program_spans_per_answer": opened / len(record.answered())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from perfbench.harness import cell as cellmod
    from perfbench.harness import runner

    if jax.devices()[0].platform != "tpu":
        print("phases.py: no TPU", file=sys.stderr)
        return 1
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = cellmod.Cell.load(ROOT, args.workload)
    m = runner.measure(cell, args.seed, args.seconds, True)
    print(json.dumps(breakdown(m.record)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
