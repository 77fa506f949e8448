"""Host seconds per answer on the chunk read path: the self time of
``read.gather``, ``eval.prepare``, ``eval.upload`` and ``eval.unpack``,
and JAX's launch and copy events inside ``planner.read``.  The fetch is
left out: it waits for the device, whose time `eval.device_s_per_answer`
reads."""
from perfbench.harness import spans


def read(record):
    if record.events is None:
        return None
    secs = spans.read_host_seconds(record.events)
    done = record.answered()
    return secs / len(done) if secs and done else None
