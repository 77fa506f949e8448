"""Device seconds of the query-eval programs in the traced window, per
answer.  The programs are found by the name below, the jitted drivers of
`repro.queries.device` as the profiler names them."""
from perfbench.harness import trace

PROGRAMS = r"^jit__eval(_nopred)?_core\("


def read(record):
    if record.events is None:
        return None
    secs = trace.module_seconds(record.events, PROGRAMS)
    done = record.answered()
    return secs / len(done) if secs > 0 and done else None
