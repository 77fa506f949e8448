"""Readings that set the limits of ``sum_rel_err`` and ``met_share``: the
program's, the control's and a stale front door's, from the same window.

    python3 perfbench/control.py --workload tpch-lineitem.adhoc-deck --seconds 15 --seeds 11 12 13

For each seed, in one process: the cell's set-up and a window at its own
load, as a benchmark run makes them; then the comparison of every chunk
read with the reference, once with the program's answers (the lower
reading) and once with the reference itself computed at a lower precision
in the program's place (the control): ``high``, the three-pass bfloat16
matmul that is the step below the float32 the configuration states, and
``bfloat16``, one pass.  Then ``stale``: each estimate of the window
compared as if served one request late, the answer of the request before
(a front door that returns the previous answer); it reads ``met_share``.
One JSON line per seed.  Needs the cell's chips, like run.py; the
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CONTROLS = {"high": 3, "bfloat16": 1}  # bfloat16 passes of the control's matmul


def stale(record):
    """``record`` as a front door serving each request the answer of the
    request before would leave it; the first request, with none before it
    in the window, is left out, and so are the chunk reads."""
    requests = [dataclasses.replace(r, answer=before.answer)
                for before, r in zip(record.requests, record.requests[1:])]
    return dataclasses.replace(record, requests=requests, chunks=[])


def readings(cell, seed: int, seconds: float, log=print, **sizes) -> dict:
    """{"program": Readings, <control>: Readings, ..., "stale": Readings}
    for one seed."""
    from perfbench.harness import check, reference, runner

    m = runner.measure(cell, seed, seconds, False, log=log, **sizes)
    bound = cell.traffic["error_bound"]
    out = {"program": check.read(m.record, m.columns, m.cardinality, bound)}
    for name, passes in CONTROLS.items():
        out[name] = check.read(m.record, m.columns, m.cardinality, bound,
                               reference.bf16_rounding(passes))
    out["stale"] = check.read(stale(m.record), m.columns, m.cardinality, bound)
    return out


def fails(r, limits: dict) -> bool:
    """Whether readings ``r`` fail a limit that the control or a stale
    door can reach."""
    return (r.sum_rel_err > limits["sum_rel_err"] or r.count_mismatch > 0
            or r.met_share < limits["answers_meeting_bound"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from perfbench.harness import cell as cellmod

    cell = cellmod.Cell.load(ROOT, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control.py: needs {cell.chips} TPU chip(s), JAX has {devices}",
              file=sys.stderr)
        return 1
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    limits = cell.config["limits"]
    for seed in args.seeds:
        r = readings(cell, seed, args.seconds)
        line = {"seed": seed, "limits": limits}
        for name, x in r.items():
            line[name] = {"sum_rel_err": x.sum_rel_err, "count_mismatch": x.count_mismatch,
                          "chunks": x.chunks_checked, "met_share": x.met_share,
                          "answers": [x.answers_met, x.answers_tested],
                          "fails": fails(x, limits)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
