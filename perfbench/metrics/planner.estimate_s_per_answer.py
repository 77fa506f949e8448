"""Host seconds per answer in ``planner.estimate``: each escalation round's
allocation over the strata (`_allocate`) and its estimate
(`stratified_answer`, `_apply_caps`)."""
from perfbench.harness import spans


def read(record):
    if record.events is None:
        return None
    secs = spans.seconds_in(record.events, "planner.estimate")
    done = record.answered()
    return secs / len(done) if secs and done else None
