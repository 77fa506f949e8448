"""One run of one cell: set-up, window, the check, the metrics, the result."""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time

import numpy as np

from perfbench.harness import check as checkmod
from perfbench.harness import serve, trace


def _p75(values) -> float | None:
    return float(np.percentile(values, 75)) if values else None


@dataclasses.dataclass
class Measured:
    record: serve.Record
    columns: dict  # the table's (P, R) arrays, for the reference
    cardinality: dict
    peak: int  # peak device bytes of the fullest chip used
    devices: list


def measure(cell, seed: int, seconds: float, traced: bool, *, t0: float | None = None,
            log=print, **sizes) -> Measured:
    """Set-up and window; the program's state is freed before it returns.
    ``t0`` is when set-up began (the process's start); ``sizes`` are for
    tests at small scale."""
    import jax

    t0 = time.perf_counter() if t0 is None else t0
    counter = serve.CompileCounter()
    prep = serve.prepare(cell, seed, log=log, **sizes)
    events = [] if traced else None
    prep.setup["total_s"] = time.perf_counter() - t0
    log("[setup] " + ", ".join(f"{k} {v:.3f}" for k, v in prep.setup.items()))

    try:
        record = serve.window(cell, prep, seconds, counter, events)
    finally:
        prep.door.stop()
    devices = jax.devices()[: cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    record.device_kind = devices[0].device_kind
    record.peaks = json.loads((cell.root / "perfbench" / "peaks.json").read_text())
    stats = prep.session.stats()
    mid = record.start + seconds / 2
    first = [r.latency for r in record.requests if r.submitted < mid]
    second = [r.latency for r in record.requests if r.submitted >= mid]
    log(f"[window] {len(record.requests)} requests, {len(record.answered())} answered, "
        f"p75 first half {_p75(first)} ({len(first)}), second half {_p75(second)} "
        f"({len(second)}), compiles {record.compiles_in_window}, chunk reads "
        f"{len(record.chunks)}, skipped repeats {record.skipped_repeats}, session "
        f"{stats['answer_hits']} hits / {stats['answer_misses']} misses, "
        f"peak_bytes_in_use {peak}")
    log("[requests] deck index, latency s, partitions read, rounds: " + json.dumps([
        [r.deck_index, round(r.latency, 4)]
        + ([r.answer.partitions_read, r.answer.plan.rounds] if r.answer is not None else [])
        for r in record.requests]))
    table = prep.table
    del prep, stats
    gc.collect()
    return Measured(record, table.columns, {s.name: s.cardinality for s in table.schema},
                    peak, devices)


def run(cell, seed: int, seconds: float, traced: bool, *, t0: float | None = None,
        log=print, **sizes) -> tuple[dict, list[checkmod.Check]]:
    """→ (the result line's object, the checks)."""
    import jax

    m = measure(cell, seed, seconds, traced, t0=t0, log=log, **sizes)
    record, devices, events = m.record, m.devices, m.record.events
    t = time.perf_counter()
    readings = checkmod.read(record, m.columns, m.cardinality, cell.traffic["error_bound"])
    checks = checkmod.checks(readings, cell.config["limits"])
    log(f"[check] {time.perf_counter() - t:.3f}s over {readings.chunks_checked} chunk reads "
        f"and {len(record.requests)} answers; {readings.answers_met} of "
        f"{readings.answers_tested} within {cell.traffic['error_bound']} "
        f"({readings.answers_empty} with an empty exact answer); misses (deck index, "
        f"error): {[(i, round(e, 4)) for i, e in readings.misses]}")

    metrics = {}
    for metric in cell.metrics:
        if (metric.kind == "per_layer") != traced:
            continue
        value = metric.reader.read(record)
        if value is not None:
            metrics[metric.name] = {"value": float(value), "unit": metric.unit}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": m.peak}
    result = {
        "correct": all(c.ok for c in checks),
        "attempted": len(record.requests),
        "failed": sum(r.answer is None for r in record.requests),
        "metrics": metrics,
        "device": device,
    }
    if traced:
        busy, window_s = trace.busy_seconds(events)
        device["busy_s"], device["window_s"] = busy, window_s
        result["breakdown"] = {"device_ops": trace.device_ops(events),
                               "idle_gaps": trace.idle_gaps(events)}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit, "op": c.op}
                        for c in checks}
    return result, checks


def emit(result: dict, checks) -> None:
    """The checks as the last lines of stderr; the result as the last of stdout."""
    sys.stdout.flush()
    for c in checks:
        print(c.line(), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
