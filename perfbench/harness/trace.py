"""The profiler's trace of a window, reduced to the numbers the metrics read.

`capture` records a trace around a block and flattens it to a list of
events ``{"plane", "line", "name", "start_ns", "dur_ns"}``, every time on
one clock.  The functions below work on that list only, so a small recorded
list (the tests' fixture) checks them without a chip.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import tempfile

WINDOW_SPAN = "bench.window"  # the host span the window runs inside
# the program's own host spans on the served path (`harness/spans.py` says
# where each opens and reduces them)
PROGRAM_SPANS = ("door.flush", "planner.select", "planner.read", "planner.merge",
                 "planner.estimate", "read.gather", "eval.prepare", "eval.upload",
                 "eval.unpack")
# an idle gap of the device is named by the innermost of these open at its
# middle: the host spans the harness opens around the calls into each
# layer, and the program's; JAX's own events are not among them
SPANS = ("bench.window", "client.wait", "serve.execute", "serve.chunk_read") + PROGRAM_SPANS
# device lines whose events are operations running on the chip
BUSY_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")
TOP = 10


@contextlib.contextmanager
def capture(out: list):
    """Trace the block; on exit ``out`` holds the flattened events."""
    import jax

    with tempfile.TemporaryDirectory(prefix="perfbench-trace-") as d:
        jax.profiler.start_trace(d)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        out.extend(flatten(files[0]))


def flatten(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                events.append({
                    "plane": plane.name, "line": line.name, "name": e.name,
                    "start_ns": float(e.start_ns), "dur_ns": float(e.duration_ns),
                })
    return events


def window(events: list[dict]) -> tuple[float, float]:
    """(start, end) in ns of the window's host span."""
    spans = [e for e in events if e["name"] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found {len(spans)}")
    e = spans[0]
    return e["start_ns"], e["start_ns"] + e["dur_ns"]


def device_planes(events: list[dict]) -> list[str]:
    return sorted({e["plane"] for e in events if e["plane"].startswith("/device:")
                   and e["line"] in BUSY_LINES})


def _clip(events, lo, hi):
    for e in events:
        a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
        if b > a:
            yield a, b


def busy_intervals(events: list[dict], plane: str, lo: float, hi: float) -> list:
    """Union of the plane's operation intervals inside [lo, hi), sorted."""
    spans = sorted(_clip((e for e in events if e["plane"] == plane
                          and e["line"] in BUSY_LINES), lo, hi))
    merged: list[list[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_seconds(events: list[dict]) -> tuple[float, float]:
    """(busy_s averaged over the chips traced, window_s)."""
    lo, hi = window(events)
    planes = device_planes(events)
    if not planes:
        return 0.0, (hi - lo) / 1e9
    busy = [sum(b - a for a, b in busy_intervals(events, p, lo, hi)) for p in planes]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def module_seconds(events: list[dict], pattern: str) -> float:
    """Device seconds of the programs whose name matches ``pattern``,
    inside the window, summed over chips."""
    lo, hi = window(events)
    rx = re.compile(pattern)
    chosen = (e for e in events if e["plane"].startswith("/device:")
              and e["line"] == "XLA Modules" and rx.search(e["name"]))
    return sum(b - a for a, b in _clip(chosen, lo, hi)) / 1e9


def op_name(name: str) -> str:
    """``%fused_eval.1 = f32[...] custom-call(...)`` → ``fused_eval``."""
    head = name.split("=", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def device_ops(events: list[dict]) -> list[list]:
    """The operations that took the most device time in the window."""
    lo, hi = window(events)
    total: dict[str, float] = {}
    for e in events:
        if e["plane"].startswith("/device:") and e["line"] == "XLA Ops":
            for a, b in _clip((e,), lo, hi):
                k = op_name(e["name"])
                total[k] = total.get(k, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:TOP]]


def idle_gaps(events: list[dict]) -> list[list]:
    """The longest idle gaps of the first chip, each named by the innermost
    of `SPANS` open at its middle ("no span open" where none is)."""
    lo, hi = window(events)
    planes = device_planes(events)
    busy = busy_intervals(events, planes[0], lo, hi) if planes else []
    gaps, t = [], lo
    for a, b in busy + [[hi, hi]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    spans = [e for e in events if e["plane"].startswith("/host:") and e["name"] in SPANS]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (a + b) / 2
        open_ = [e for e in spans if e["start_ns"] <= mid < e["start_ns"] + e["dur_ns"]]
        name = min(open_, key=lambda e: e["dur_ns"])["name"] if open_ else "no span open"
        out.append([name, (b - a) / 1e9])
    return out
