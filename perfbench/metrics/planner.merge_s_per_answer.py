"""Host seconds per answer in ``planner.merge``: `_merge_raw` folding chunk
reads into the answer's accumulated (partitions, groups, channels) array,
and the row map's update."""
from perfbench.harness import spans


def read(record):
    if record.events is None:
        return None
    secs = spans.seconds_in(record.events, "planner.merge")
    done = record.answered()
    return secs / len(done) if secs and done else None
