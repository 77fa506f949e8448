"""Set-up and the measured window of a cell whose traffic is a query deck.

Set-up builds the table from the seed, the sketches, and the picker
(`Session.prepare`), then warms every eval shape of the deck: each
template, with constants the window never asks for, is read once on one
chunk, and a few go through the front door and planner whole.

The window plays the deck as the traffic file says, and no file of a
traffic mix needs code of its own:

* ``clients``: that many clients in a closed loop, each submitting the
  deck's next template through `FrontDoor`, waiting for its answer, then
  ``think_s`` seconds, then submitting the next;
* ``deck_order``: ``"once"`` plays the deck from its start in file order,
  never asks a query text twice and fails past the deck's end (ad hoc
  analysts); ``"cycle"`` plays it in file order and starts again at its
  end, so the same queries repeat (dashboards refreshing their panels).

Each request is timed on its client's clock from submit to answer.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from perfbench.harness import deck as deckmod

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
DECK_ORDERS = ("once", "cycle")


@dataclasses.dataclass
class Request:
    deck_index: int
    query: dict  # the instantiated template
    query_text: str  # the program's text of it
    submitted: float  # client clock, seconds
    done: float
    answer: object  # PlannedAnswer, or None where it never came
    error: str | None = None

    @property
    def latency(self) -> float:
        return self.done - self.submitted


@dataclasses.dataclass
class ChunkRead:
    query_text: str
    ids: np.ndarray
    group_keys: np.ndarray
    raw: np.ndarray


@dataclasses.dataclass
class Record:
    """What a run saw; the metric readers read it."""

    seconds: float  # the window asked for
    start: float  # client clock at the window's start
    requests: list[Request] = dataclasses.field(default_factory=list)
    chunks: list[ChunkRead] = dataclasses.field(default_factory=list)
    setup: dict = dataclasses.field(default_factory=dict)  # phase → seconds
    compiles_in_window: int = 0
    skipped_repeats: int = 0
    events: list | None = None  # the trace, with --trace 1
    device_kind: str = ""
    rows_per_partition: int = 0
    peaks: dict = dataclasses.field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.seconds

    def answered(self) -> list[Request]:
        return [r for r in self.requests if r.answer is not None]


class CompileCounter:
    """Programs JAX lowers while ``counting`` is set (a new trace or shape)."""

    def __init__(self):
        import jax

        self.counting = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if self.counting and event == COMPILE_EVENT:
            self.count += 1


class Instrumented:
    """Spans around the calls into the serving layers, and a record of the
    planner's chunk reads while ``recording`` is set.  Wraps the session's
    own bound methods, which the front door and planner call through."""

    def __init__(self, session):
        import jax

        self.recording = False
        self.chunks: list[ChunkRead] = []
        annotate = jax.profiler.TraceAnnotation
        execute, get_subset = session.execute, session.answers.get_subset

        def traced_execute(spec, **kw):
            with annotate("serve.execute"):
                return execute(spec, **kw)

        def traced_get_subset(query, part_ids):
            with annotate("serve.chunk_read"):
                ans = get_subset(query, part_ids)
            if self.recording:
                self.chunks.append(ChunkRead(query.describe(), np.array(part_ids, np.int64),
                                             np.asarray(ans.group_keys), np.asarray(ans.raw)))
            return ans

        session.execute = traced_execute
        session.answers.get_subset = traced_get_subset


@dataclasses.dataclass
class Prepared:
    table: object
    session: object
    door: object
    probe: Instrumented
    queries: list[dict]  # the deck, instantiated for this table
    texts: list[str]
    program_queries: list  # the program's IR of each
    setup: dict


def prepare(cell, seed: int, log=print, *, num_partitions=None, rows_per_partition=None,
            prepare_kw=None) -> Prepared:
    """Everything before the window; ``setup`` holds each phase's seconds.
    The picker trains as the configuration's ``picker`` says; the keyword
    sizes and ``prepare_kw`` are for tests at small scale only."""
    import repro.api as ps3
    from repro.core.picker import PickerConfig
    from repro.queries.generator import WorkloadSpec
    from repro.serving import FrontDoor

    setup = {}
    t = time.perf_counter()
    table = cell.build_table(seed, num_partitions, rows_per_partition)
    setup["build_table_s"] = time.perf_counter() - t

    header, templates = deckmod.load(cell.deck_path)
    numeric = [s.name for s in table.schema if s.kind == "numeric"]
    levels = deckmod.quantile_levels(header)
    quantiles = deckmod.column_quantiles(table.columns, numeric, levels)
    queries = [deckmod.instantiate(tp, quantiles) for tp in templates]
    program_queries = [deckmod.to_query(q) for q in queries]
    texts = [q.describe() for q in program_queries]

    t = time.perf_counter()
    sess = ps3.Session(table)  # builds the sketches
    setup["sketch_build_s"] = time.perf_counter() - t
    t = time.perf_counter()
    if prepare_kw is None:
        pk = cell.config["picker"]
        prepare_kw = {"num_train_queries": pk["num_train_queries"],
                      "picker_config": PickerConfig(num_trees=pk["num_trees"],
                                                    tree_depth=pk["tree_depth"])}
    sess.prepare(WorkloadSpec(table, seed=seed), **prepare_kw)
    setup["picker_train_s"] = time.perf_counter() - t

    t = time.perf_counter()
    probe = Instrumented(sess)
    half = deckmod.column_quantiles(table.columns, numeric, deckmod.midpoint_levels(levels))
    taken = {deckmod.key(q) for q in queries}
    warm = [deckmod.warm_variant(tp, half, taken) for tp in templates]
    ids = np.arange(min(sess.planner_config.chunk, table.num_partitions), dtype=np.int64)
    for q in warm:
        sess.answers.get_subset(deckmod.to_query(q), ids)
    door = FrontDoor(sess).start()
    bound = cell.traffic["error_bound"]
    for q in warm[: cell.traffic["warm_executes"]]:
        door.submit(ps3.QuerySpec(deckmod.to_query(q), error_bound=bound),
                    tenant=cell.traffic["tenant"]).result(timeout=600)
    setup["warmup_s"] = time.perf_counter() - t
    log(f"[warmup] {len(warm)} deck shapes read once on {ids.size} partitions, "
        f"{cell.traffic['warm_executes']} served whole, {setup['warmup_s']:.3f}s")
    return Prepared(table, sess, door, probe, queries, texts, program_queries, setup)


def window(cell, prep: Prepared, seconds: float, counter: CompileCounter,
           events: list | None = None) -> Record:
    """The traffic's clients for ``seconds``; each request in flight at the
    close is awaited (its latency counts, its answer is past the window)."""
    import jax

    import repro.api as ps3
    from perfbench.harness import trace
    from repro.errors import OverloadError

    tr = cell.traffic
    bound, tenant, wait = tr["error_bound"], tr["tenant"], tr["answer_wait_s"]
    clients, think, order = int(tr["clients"]), float(tr["think_s"]), tr["deck_order"]
    if clients < 1 or think < 0 or order not in DECK_ORDERS:
        raise ValueError(f"traffic {tr['name']}: clients {clients} (>= 1), think_s {think} "
                         f"(>= 0), deck_order {order!r} (one of {DECK_ORDERS})")
    annotate = jax.profiler.TraceAnnotation
    prep.probe.chunks = []
    record = Record(seconds=seconds, start=0.0)
    lock = threading.Lock()
    taken: set[str] = set()
    cursor = 0
    n = len(prep.queries)

    def take() -> int:
        """The deck index the next request asks."""
        nonlocal cursor
        with lock:
            if order == "cycle":
                cursor += 1
                return (cursor - 1) % n
            while cursor < n and prep.texts[cursor] in taken:
                record.skipped_repeats += 1  # a template equal, on this table, to one taken
                cursor += 1
            if cursor >= n:
                raise deckmod.DeckExhausted(
                    f"the window asked for template {cursor + 1} of a deck of "
                    f"{n} ({cell.deck_path.name}); a deck played once never wraps")
            taken.add(prep.texts[cursor])
            cursor += 1
            return cursor - 1

    def submit(spec):
        """The door's ticket; a rate-limited client waits the exact
        ``retry_after`` the door gives and asks again (the wait counts in
        the latency).  Any other refusal fails the request."""
        while True:
            try:
                return prep.door.submit(spec, tenant=tenant)
            except OverloadError as e:
                if e.reason != "rate_limited":
                    raise
                time.sleep(e.retry_after)

    def client():
        while time.perf_counter() < record.end:
            i = take()
            spec = ps3.QuerySpec(prep.program_queries[i], error_bound=bound)
            t0 = time.perf_counter()
            answer, error = None, None
            with annotate("client.wait"):
                try:
                    answer = submit(spec).result(timeout=max(record.end - t0, 0) + wait)
                except Exception as e:  # refused, never resolved, or carrying an error
                    error = repr(e)
            request = Request(i, prep.queries[i], prep.texts[i], t0, time.perf_counter(),
                              answer, error)
            with lock:
                record.requests.append(request)
            if think:
                time.sleep(think)

    def play():
        record.start = time.perf_counter()
        if clients == 1:
            client()
            return
        failures = []

        def guarded():
            try:
                client()
            except BaseException as e:  # re-raised in the caller's thread
                failures.append(e)

        threads = [threading.Thread(target=guarded, name=f"perfbench-client-{k}")
                   for k in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if failures:
            raise failures[0]

    prep.probe.recording = True
    counter.count, counter.counting = 0, True
    try:
        if events is None:
            play()
        else:
            with trace.capture(events):
                with annotate(trace.WINDOW_SPAN):
                    play()
    finally:
        counter.counting = False
        prep.probe.recording = False
    record.requests.sort(key=lambda r: r.submitted)
    record.compiles_in_window = counter.count
    record.chunks = prep.probe.chunks
    record.events = events
    record.setup = dict(prep.setup)
    record.rows_per_partition = prep.table.rows_per_partition
    return record
