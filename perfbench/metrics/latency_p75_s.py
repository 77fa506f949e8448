"""75th percentile of the client's latency, submit to answer, over every
request of the window (the one in flight at the close included)."""
import numpy as np


def read(record):
    lat = [r.latency for r in record.requests]
    return float(np.percentile(lat, 75)) if lat else None
