"""Share of the window's device-idle time in which the flush thread's
innermost program span is the planner's (``planner.*``), instant by
instant."""
from perfbench.harness import spans


def read(record):
    if record.events is None:
        return None
    shares = spans.idle_shares(record.events)
    return None if shares is None else shares.get("planner", 0.0)
