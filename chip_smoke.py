"""On-chip smoke test of the PS³ main path, through the public entry points.

    python chip_smoke.py            # one TPU chip
    python chip_smoke.py --chips 4  # partition mesh over four chips vs one

One chip, one process, in order:

  a. build the TPC-H-like lineitem table from its seed: 16 columns on the
     date-sorted layout, 1,024 partitions × 16,384 rows (16.8 M rows);
  b. ``Session(table).prepare(...)`` with default `ExecOptions` (device
     backend, native Pallas kernels) and the picker settings of
     ``examples/aqp_service.py``;
  c. serve 16 unseen ``QuerySpec(error_bound=0.05)``: half through
     ``Session.execute``, half through a started `FrontDoor` on the real
     clock;
  d. hold the chip to host numpy: the served queries' per-partition
     answers (counts exact, sums rtol 1e-5) and the sketches (allclose);
     report how the importance funnel fit on those queries on the device
     compares with the host fit.

With ``--chips 4`` it runs only the partition-mesh path — sketch
statistics, per-partition answers and a few ``Session.execute`` calls
under ``ExecOptions(mesh=4)`` — against the same work on one device, and
holds them bitwise equal.

Each phase prints a line: wall seconds split into JAX trace/compile time
and the rest, partitions read, whether each answer met its bound and how
many of its confidence intervals covered the exact value, peak device
bytes, and queries that took the host fallback.  Coverage counts only
answers whose exact result is non-empty; the empty ones are listed
apart.  The last line of stdout is the JSON verdict.  A failed check, a
`FrontDoor` ticket that carries an exception, a stray
``REPRO_EVAL_BACKEND`` / ``REPRO_MESH``, or a platform other than TPU
exits nonzero without it.  JAX's compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` beside this
file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PARTITIONS, ROWS, SEED = 1024, 16384, 0
TEST_SEED, NUM_QUERIES, BOUND = 777, 16, 0.05
MIN_COVERAGE = 0.9
SUM_RTOL, SUM_ATOL = 1e-5, 1e-4  # as tests/test_query_device.py
MEASURE_TOL = 2e-4  # as tests/test_ingest_parity.py
PICKER = {"num_trees": 24, "tree_depth": 4}  # examples/aqp_service.py
# top-level lowering + XLA compile (trace events nest, so they are left out)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Running total of JAX's lowering + compile seconds."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += secs
            self.programs += event == COMPILE_EVENTS[-1]


class Phase:
    """Times one phase; prints wall = compile + rest, and peak device bytes."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock
        self.notes: list[str] = []

    def __enter__(self):
        self.c0, self.n0 = self.clock.seconds, self.clock.programs
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        wall = time.perf_counter() - self.t0
        comp = self.clock.seconds - self.c0
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
        peak = peaks[0] if len(peaks) == 1 else peaks
        extra = "".join(f" | {n}" for n in self.notes)
        log(
            f"[{self.name}] wall {wall:.3f}s = compile {comp:.3f}s "
            f"({self.clock.programs - self.n0} programs) + warm {wall - comp:.3f}s"
            f" | peak_bytes_in_use {peak}{extra}"
        )
        return False


def build_table():
    from repro.data.datasets import make_dataset

    return make_dataset(
        "tpch", num_partitions=PARTITIONS, rows_per_partition=ROWS, seed=SEED,
        layout="sorted",
    )


def prepare(sess, num_train_queries: int = 60, **picker):
    """`Session.prepare` with the example service's picker settings."""
    from repro.core.picker import PickerConfig
    from repro.queries.generator import WorkloadSpec

    config = PickerConfig(**{**PICKER, **picker})
    return sess.prepare(
        WorkloadSpec(sess.table, seed=SEED), num_train_queries=num_train_queries,
        picker_config=config,
    )


def test_specs(table, n: int = NUM_QUERIES):
    import repro.api as ps3
    from repro.queries.generator import WorkloadSpec

    queries = WorkloadSpec(table, seed=TEST_SEED).sample_workload(n)
    return [ps3.QuerySpec(q, error_bound=BOUND) for q in queries]


# --------------------------------------------------------------------------
# checks against host numpy
# --------------------------------------------------------------------------
def answers_agree(host, dev, exact: bool = False) -> str | None:
    """None when per-partition answers agree, else what differs."""
    import numpy as np

    if not np.array_equal(host.group_keys, dev.group_keys):
        return "group keys differ"
    if not np.array_equal(host.raw[..., 0], dev.raw[..., 0]):
        return "counts differ"
    if exact:
        return None if np.array_equal(host.raw, dev.raw) else "sums not bitwise"
    if not np.allclose(dev.raw, host.raw, rtol=SUM_RTOL, atol=SUM_ATOL):
        err = np.abs(dev.raw - host.raw) / np.maximum(np.abs(host.raw), 1e-12)
        return f"sums differ (max rel err {float(err.max()):.3e})"
    return None


def sketches_agree(host, dev) -> list[str]:
    import numpy as np

    bad = []
    for name, h in host.columns.items():
        d = dev.columns[name]
        if not np.allclose(d.measures, h.measures, rtol=MEASURE_TOL, atol=MEASURE_TOL):
            worst = np.abs(d.measures - h.measures) - MEASURE_TOL * (1 + np.abs(h.measures))
            bad.append(f"{name}.measures (worst column {int(worst.max(axis=0).argmax())})")
        for field in ("cat_counts", "hh_stats", "bitmap", "global_hh"):
            a, b = getattr(h, field), getattr(d, field)
            if (a is None) != (b is None) or (a is not None and not np.array_equal(a, b)):
                bad.append(f"{name}.{field}")
        if h.hh_items != d.hh_items:
            bad.append(f"{name}.hh_items")
    return bad


def compare_forests(table, sketches, queries, host_ans) -> str:
    """Fit the importance funnel on host and device → a report line.

    The funnel is fit as `Session.prepare` fits it, on the features and
    exact contributions of ``queries``.  No agreement is promised, so
    nothing is checked: where split gains nearly tie, re-associated
    histogram sums (the MXU's order on a TPU) pick another split and every
    later tree follows.  The line gives matching split structures, the
    largest prediction gap and each device forest's training loss over
    the host forest's.
    """
    import numpy as np

    from repro.core.features import FeatureBuilder
    from repro.core.funnel import make_labels, train_funnel

    fb = FeatureBuilder(table, sketches)
    feats = [fb.features(q) for q in queries]
    contribs = [a.contribution() for a in host_ans]
    x = np.concatenate(feats)
    fit, secs = {}, {}
    for backend in ("host", "device"):
        t0 = time.perf_counter()
        fit[backend] = train_funnel(
            feats, contribs, num_trees=PICKER["num_trees"], depth=PICKER["tree_depth"],
            backend=backend,
        )
        secs[backend] = time.perf_counter() - t0
    splits = leaves = 0
    worst, ratios = 0.0, []
    host, dev = fit["host"], fit["device"]
    for t, h, d in zip(host.thresholds, host.forests, dev.forests):
        y = np.concatenate([make_labels(c, t)[0] for c in contribs])
        splits += np.array_equal(h.feat, d.feat) and np.array_equal(h.thr, d.thr)
        leaves += np.array_equal(h.leaf, d.leaf)
        ph, pd = h.predict(x), d.predict(x)
        worst = max(worst, float(np.abs(pd - ph).max()))
        ratios.append(float(np.mean((pd - y) ** 2) / np.mean((ph - y) ** 2)))
    n = len(host.forests)
    return (
        f"gbdt: {n} forests x {PICKER['num_trees']} trees on {x.shape[0]} rows, "
        f"host {secs['host']:.3f}s, device {secs['device']:.3f}s, "
        f"splits identical {splits}/{n}, leaves bitwise {leaves}/{n}, "
        f"pred max abs diff {worst:.3e}, device/host training loss "
        + " ".join(f"{r:.4f}" for r in ratios)
    )


def served_line(i: int, spec, ans, truth_ans, timing: str) -> tuple[str, bool, bool]:
    """One served answer against the exact one →
    (line, met its bound, the exact answer has a cell to estimate)."""
    import numpy as np

    from repro.queries.engine import error_metrics

    truth = truth_ans.truth()
    est = np.full(truth.shape, np.nan)
    hw = np.full(truth.shape, np.nan)
    pos = {int(k): j for j, k in enumerate(ans.group_keys)}
    for gi, k in enumerate(truth_ans.group_keys):
        j = pos.get(int(k))
        if j is not None:
            est[gi], hw[gi] = ans.estimate[j], ans.ci_halfwidth[j]
    err = error_metrics(truth, est)["avg_rel_err"] if truth.size else 0.0
    live = ~np.isnan(truth)
    slack = 1e-6 * np.abs(truth) + 1e-9  # float noise of an exact answer
    covered = int(np.sum(live & (np.abs(est - truth) <= hw + slack)))
    met = err <= spec.error_bound
    line = (
        f"  q{i:02d} {ans.plan.mode:7s} read {ans.partitions_read:4d}/{PARTITIONS} "
        f"{timing} err {err:.4f} "
        f"{'<=' if met else '>'} {spec.error_bound} "
        f"ci_covers {covered}/{int(live.sum())}"
    )
    return line, met, bool(live.any())


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------
def run_one_chip(clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp

    import repro.api as ps3
    from repro.core import ingest
    from repro.core.sketches import build_sketches
    from repro.queries import device
    from repro.queries.engine import EvalCache, per_partition_answers_batch, stack_partitions
    from repro.serving import FrontDoor

    opts = ps3.ExecOptions()
    check(opts.resolved_backend() == "device", "default backend is not 'device'")
    check(opts.kernels_ref() is False, "device backend would run the jnp oracles")
    check(opts.plane() is None, "default options resolve a partition mesh")
    host = ps3.ExecOptions(backend="host")

    with Phase("a build_table", clock) as ph:
        table = build_table()
        ph.notes.append(f"{table.num_partitions} x {table.rows_per_partition} rows, "
                        f"{len(table.schema)} columns")

    with Phase("b prepare", clock):
        sess = prepare(ps3.Session(table, options=opts))

    # the compiled programs of the main path carry Mosaic kernels
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    ingest_hlo = ingest._moments_jit.lower(
        sds((PARTITIONS, ROWS), f32), use_ref=opts.kernels_ref()
    ).compile().as_text()
    check("tpu_custom_call" in ingest_hlo, "ingest program has no tpu_custom_call")
    ncols1 = len(table.schema) + 1  # columns + the ones column
    stack = sds((ncols1, stack_partitions(PARTITIONS), ROWS), f32)
    eval_hlo = device._eval_stacked.lower(
        stack, sds((1, 4), i32), sds((1, 4), f32), sds((1, 4), f32),
        sds((1, 4, 2), f32), sds((1, 4, ncols1), f32), sds((1, ncols1), f32),
        num_groups=2, radix=8, use_ref=opts.kernels_ref(),
    ).compile().as_text()
    check("tpu_custom_call" in eval_hlo, "eval program has no tpu_custom_call")
    log("[b prepare] tpu_custom_call in the ingest and eval programs: yes")

    specs = test_specs(table)
    half = len(specs) // 2
    # queries the device eval would send to the host path instead
    _, fallback = device._plan_workload(table, [s.query for s in specs], EvalCache(table))
    fallbacks = [f"q{i:02d} {q.describe()}" for i, q in fallback]
    served: list[tuple[int, object, str]] = []  # (index, answer, timing)
    with Phase("c1 Session.execute", clock) as ph:
        for i, spec in enumerate(specs[:half]):
            c0, t0 = clock.seconds, time.perf_counter()
            ans = sess.execute(spec)
            wall, comp = time.perf_counter() - t0, clock.seconds - c0
            served.append((i, ans, f"execute wall {wall:.3f}s (compile {comp:.3f}s)"))
        ph.notes.append(f"{half} queries")

    with Phase("c2 FrontDoor", clock) as ph:
        door = FrontDoor(sess).start()
        try:
            t0 = time.perf_counter()
            tickets = [door.submit(s, tenant="smoke") for s in specs[half:]]
            for i, t in enumerate(tickets, start=half):
                try:
                    ans = t.result(timeout=600)
                except Exception as e:  # a ticket that carries an exception
                    raise SmokeFailure(f"FrontDoor ticket q{i:02d} raised {e!r}") from e
                served.append((i, ans, f"door latency {t.latency:.3f}s "
                                       f"(queued {t.queue_seconds:.3f}s)"))
            wall = time.perf_counter() - t0
        finally:
            door.stop()
        st = door.serve_stats()
        ph.notes.append(
            f"{len(tickets)} tickets in {wall:.3f}s, completed {st['completed']}, "
            f"degraded {st['degraded_answers']}, shed {st['sheds']}"
        )

    with Phase("d exactness", clock) as ph:
        queries = [s.query for s in specs]
        t0 = time.perf_counter()
        dev_ans = sess.answers.get_batch(queries)  # device, full table
        t_dev = time.perf_counter() - t0
        t0 = time.perf_counter()
        host_ans = per_partition_answers_batch(table, queries, options=host)
        t_host = time.perf_counter() - t0
        mismatched = [
            f"q{i:02d}: {why}" for i, (h, d) in enumerate(zip(host_ans, dev_ans))
            if (why := answers_agree(h, d)) is not None
        ]
        ph.notes.append(f"answers: device {t_dev:.3f}s, host {t_host:.3f}s, "
                        f"{len(queries) - len(mismatched)}/{len(queries)} agree")
        t0 = time.perf_counter()
        host_sk = build_sketches(table, options=host)
        bad_sk = sketches_agree(host_sk, sess.sketches.sketches())
        ph.notes.append(f"sketches: host {time.perf_counter() - t0:.3f}s, "
                        f"{len(bad_sk)} mismatched fields")
        ph.notes.append(compare_forests(table, sess.sketches.sketches(), queries, host_ans))

    met, empty = [], []
    for i, ans, timing in sorted(served, key=lambda s: s[0]):
        line, ok, tested = served_line(i, specs[i], ans, host_ans[i], timing)
        if tested:
            met.append(ok)
        else:
            empty.append(f"q{i:02d}")
        log(line)
    check(bool(met), "no served query has a non-empty exact answer")
    coverage = sum(met) / len(met)
    reads = [ans.partitions_read for _, ans, _ in served]
    log(f"[serve] {len(served)} answers: {len(met)} with a non-empty exact answer, "
        f"{sum(met)} of them within {BOUND} (coverage {coverage:.4f}); "
        f"{len(empty)} with an empty one {empty}; "
        f"mean partitions read {sum(reads) / len(reads):.2f}/{PARTITIONS}")
    log(f"[serve] host fallbacks: {len(fallbacks)} {fallbacks}")
    for m in mismatched:
        log(f"[d exactness] MISMATCH {m}")
    for b in bad_sk:
        log(f"[d exactness] SKETCH MISMATCH {b}")
    check(not mismatched, f"{len(mismatched)} per-partition answers differ from host")
    check(not bad_sk, f"sketch fields differ from host: {bad_sk}")
    check(coverage >= MIN_COVERAGE, f"coverage {coverage:.3f} < {MIN_COVERAGE}")


# --------------------------------------------------------------------------
# four chips: partition mesh vs one device
# --------------------------------------------------------------------------
def run_mesh(clock: CompileClock, chips: int) -> None:
    import numpy as np

    import repro.api as ps3
    from repro.core.ingest import build_statistics
    from repro.queries.engine import per_partition_answers_batch

    one = ps3.ExecOptions(mesh=None)
    mesh = ps3.ExecOptions(mesh=chips)
    check(mesh.plane() is not None and mesh.plane().num_devices == chips,
          f"no {chips}-device partition mesh")

    with Phase("a build_table", clock):
        table = build_table()

    stats = {}
    for name, opts in (("one", one), ("mesh", mesh)):
        with Phase(f"m1 sketch statistics [{name}]", clock):
            stats[name] = build_statistics(table, discrete_counts=True, options=opts)
    diff = [
        f"{col}.{k}" for col, t in stats["one"].items() for k, v in t.items()
        if not np.array_equal(np.asarray(v), np.asarray(stats["mesh"][col][k]))
    ]
    log(f"[m1] sketch statistics bitwise equal: {not diff} {diff}")

    specs = test_specs(table, 8)
    queries = [s.query for s in specs]
    ans = {}
    for name, opts in (("one", one), ("mesh", mesh)):
        with Phase(f"m2 per-partition answers [{name}]", clock):
            ans[name] = per_partition_answers_batch(table, queries, options=opts)
    answers_diff = [
        f"q{i:02d}: {why}" for i, (a, b) in enumerate(zip(ans["one"], ans["mesh"]))
        if (why := answers_agree(a, b, exact=True)) is not None
    ]
    log(f"[m2] per-partition answers bitwise equal: {not answers_diff} {answers_diff}")

    served = {}
    for name, opts in (("one", one), ("mesh", mesh)):
        with Phase(f"m3 prepare + Session.execute [{name}]", clock):
            # a small picker: the comparison needs the same one on both sides
            sess = prepare(ps3.Session(table, options=opts), num_train_queries=8,
                           num_trees=8, feature_selection=False)
            served[name] = [sess.execute(s) for s in specs[:4]]
    exec_diff = [
        f"q{i:02d}" for i, (a, b) in enumerate(zip(served["one"], served["mesh"]))
        if not (np.array_equal(a.group_keys, b.group_keys)
                and np.array_equal(a.estimate, b.estimate, equal_nan=True)
                and a.partitions_read == b.partitions_read)
    ]
    log(f"[m3] Session.execute estimates bitwise equal: {not exec_diff} {exec_diff}")
    # a partition's reductions stay on one device in the same f32 order,
    # so the mesh owes bitwise equality (docs/architecture.md, Invariants)
    check(not diff, f"mesh sketch statistics differ from one device: {diff}")
    check(not answers_diff, f"mesh per-partition answers differ: {answers_diff}")
    check(not exec_diff, f"mesh Session.execute estimates differ: {exec_diff}")
    log("[mesh] verdict: statistics, per-partition answers and execute estimates "
        "bitwise equal to one device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    for var in ("REPRO_EVAL_BACKEND", "REPRO_MESH"):
        if os.environ.get(var):
            print(f"chip_smoke: {var}={os.environ[var]!r} would reroute the main "
                  "path; unset it", file=sys.stderr)
            return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {Path(__file__).name}; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from repro.backends import use_checkout_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 1
    cache = use_checkout_compile_cache(ROOT)
    log(f"[env] jax {jax.__version__}, {len(devices)} x {devices[0].device_kind}, "
        f"compile cache {cache}")
    clock = CompileClock()
    try:
        if args.chips == 1:
            run_one_chip(clock)
        else:
            run_mesh(clock, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
