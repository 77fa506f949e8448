"""Share of the traced window in which no operation ran on the chip:
100 * (1 - busy / window), busy being the union of the device's operation
intervals."""
from perfbench.harness import trace


def read(record):
    if record.events is None:
        return None
    busy, window = trace.busy_seconds(record.events)
    if not trace.device_planes(record.events):
        return None
    return 100.0 * (1.0 - busy / window)
