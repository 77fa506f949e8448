"""Share of the window's device-idle time in which the flush thread is on
the chunk read path: its innermost program span is ``read.*`` or
``eval.*``, or it is in JAX's launch, copy or fetch inside
``planner.read``."""
from perfbench.harness import spans


def read(record):
    if record.events is None:
        return None
    shares = spans.idle_shares(record.events)
    return None if shares is None else shares.get("read", 0.0)
