"""The program's own host spans in a traced window, reduced to host time per
phase and to what the host was doing while the chip sat idle.

The program opens these spans (`jax.profiler.TraceAnnotation`) on the
served path, all on the front door's flush thread:

    door.flush        serving/frontdoor.py  FrontDoor.tick, a non-empty flush
    planner.select    planner/planner.py    QueryPlanner.answer up to the strata
    planner.read      planner/planner.py    QueryPlanner._read, the chunk loop
    planner.merge     planner/planner.py    _merge_raw folding chunk reads into
                                            the answer's array
    planner.estimate  planner/planner.py    a round's allocation; its estimate
    read.gather       queries/engine.py     AnswerStore.get_subset on a miss
    eval.prepare      queries/device.py     canonical predicates; descriptors
    eval.upload       queries/engine.py     EvalCache.device_stack, building
    eval.unpack       queries/device.py     answers from the fetched sums

Between them the chunk read calls JAX, whose own host events are read here
and not wrapped again: the launch (``PjitFunction(...)``), the copy to the
device (``DevicePut...``) and the fetch (``np.asarray(jax.Array)``).

Events on one thread nest.  The flattened trace names every Python
thread's line after the process, so an event's parent is the innermost
event of the same line name that holds it; the program's spans and JAX's
events come from the flush thread alone, and the other threads' spans
(the harness's) are left out before nesting.  A span's self time is the
part of it that no program span inside it covers; JAX's events count to
the program span they sit in.
"""
from __future__ import annotations

from perfbench.harness import trace

PROGRAM = trace.PROGRAM_SPANS
LAUNCH = ("PjitFunction(", "DevicePut")  # name prefixes
FETCH = "np.asarray(jax.Array)"


def _kept(name: str) -> bool:
    return name in PROGRAM or name == FETCH or name.startswith(LAUNCH)


def stretches(events: list[dict]) -> list[tuple[float, float, tuple[str, ...]]]:
    """``(start, end, path)`` in ns, inside the window: each stretch in which
    the event named last in ``path`` is the innermost open on its line,
    ``path`` naming the events that hold it, outermost first."""
    lo, hi = trace.window(events)
    lines: dict[tuple[str, str], list[dict]] = {}
    for e in events:
        if e["plane"].startswith("/host:") and _kept(e["name"]):
            lines.setdefault((e["plane"], e["line"]), []).append(e)
    out = []
    for line in lines.values():
        line.sort(key=lambda e: (e["start_ns"], -e["dur_ns"]))
        nodes: list[tuple[float, float, tuple[str, ...], list]] = []
        open_: list[int] = []  # nodes not yet closed, by start
        for e in line:
            a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
            open_ = [i for i in open_ if nodes[i][1] > a]
            parent = next((i for i in reversed(open_) if nodes[i][1] >= b), None)
            path = (nodes[parent][2] if parent is not None else ()) + (e["name"],)
            if parent is not None:
                nodes[parent][3].append((a, b))
            open_.append(len(nodes))
            nodes.append((a, b, path, []))
        for a, b, path, kids in nodes:
            t = a
            for ka, kb in sorted(kids) + [(b, b)]:
                s, u = max(t, lo), min(ka, hi)
                if u > s:
                    out.append((s, u, path))
                t = max(t, kb)
    return out


def program_span(path: tuple[str, ...]) -> str | None:
    """The innermost program span of a path (None: outside them all)."""
    return next((n for n in reversed(path) if n in PROGRAM), None)


def layer(path: tuple[str, ...]) -> str | None:
    """``"planner"``, ``"read"`` or ``"door"``: the layer of the innermost
    program span, except that JAX's events inside ``planner.read`` count to
    the read (the chunk read calls them between its ``eval.*`` spans)."""
    span = program_span(path)
    if span is None:
        return None
    if span.startswith(("read.", "eval.")) or (span == "planner.read"
                                               and path[-1] not in PROGRAM):
        return "read"
    return span.split(".")[0]


def self_seconds(events: list[dict]) -> dict[str, float]:
    """Self time of each program span name, summed over the window."""
    out: dict[str, float] = {}
    for a, b, path in stretches(events):
        span = program_span(path)
        if span is not None:
            out[span] = out.get(span, 0.0) + (b - a) / 1e9
    return out


def seconds_in(events: list[dict], name: str) -> float:
    """Time inside spans of ``name``, its children included."""
    return sum(b - a for a, b, path in stretches(events) if name in path) / 1e9


def read_host_seconds(events: list[dict]) -> float:
    """Host time of the read path: self time of ``read.*`` and ``eval.*``
    spans and JAX's launch and copy events in ``planner.read``; the fetch,
    which waits for the device, is left out."""
    return sum(b - a for a, b, path in stretches(events)
               if layer(path) == "read" and FETCH not in path) / 1e9


def idle_shares(events: list[dict]) -> dict[str, float] | None:
    """Share (%) of the window's device-idle time, on the first chip, in
    which the flush thread's innermost program span is of each layer; None
    without a device or a ``door.flush`` span."""
    lo, hi = trace.window(events)
    planes = trace.device_planes(events)
    flush = sorted((a, b, path) for a, b, path in stretches(events)
                   if path[0] == "door.flush")
    if not planes or not flush:
        return None
    idle, t = [], lo
    for a, b in trace.busy_intervals(events, planes[0], lo, hi) + [[hi, hi]]:
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    total = sum(b - a for a, b in idle)
    if total <= 0:
        return None
    out: dict[str, float] = {}
    i = 0
    for a, b, path in flush:
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < b:
            overlap = min(b, idle[j][1]) - max(a, idle[j][0])
            name = layer(path)
            out[name] = out.get(name, 0.0) + 100.0 * overlap / total
            j += 1
    return out
