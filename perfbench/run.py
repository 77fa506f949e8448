"""Run one cell of the benchmark once, on the chips of this machine.

    python3 perfbench/run.py --workload tpch-lineitem.adhoc-deck --seed 7 --seconds 51 --trace 0

From the root of a checkout.  Builds the configuration's table from the
seed, prepares a `repro` Session on it, warms every eval shape of the
traffic's deck, then measures for ``--seconds``.  ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window.  Every answer of the window is then checked
against a plain numpy reference (`harness/check.py`); each number compared
is printed beside its limit, as the last lines of stderr and under
``checks`` in the result.  The last line of stdout is the result as JSON.

Exits 1, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for; 2 where the checkout lacks the program.  JAX's
persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache/`` at the checkout's root.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0 or args.seconds <= 0:
        print("run.py: --seed must be >= 0 and --seconds > 0", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: no src/repro under {ROOT}; run it from a checkout of the "
              "repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from perfbench.harness import cell as cellmod
    from perfbench.harness import deck as deckmod
    from perfbench.harness import runner

    cell = cellmod.Cell.load(ROOT, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX platform {devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} chips, JAX has "
              f"{len(devices)}", file=sys.stderr)
        return 1
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"[env] jax {jax.__version__}, {len(devices)} x {devices[0].device_kind}, "
          f"compile cache {cache}, cell {args.workload}, seed {args.seed}", flush=True)

    try:
        result, checks = runner.run(cell, args.seed, args.seconds, bool(args.trace), t0=T0)
    except deckmod.DeckExhausted as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    runner.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
