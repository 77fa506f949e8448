"""Share of the eval programs' device time that the bytes the queries need
would take at the chip's HBM bandwidth: 100 * (bytes / peak) / time.

The bytes are counted from the queries and the chunk reads alone: for each
chunk read, its distinct partitions' rows times the distinct columns its
query touches times 4 bytes, plus its answer (partitions x occupied
groups x channels x 4 bytes).  Padding the program adds (repeated
partitions, tiles, the ones column) is not needed work and is not counted.
"""
from perfbench.harness import trace

PROGRAMS = r"^jit__eval(_nopred)?_core\("
BYTES = 4  # float32 and int32 columns


def needed_bytes(record) -> float:
    columns = {}
    for r in record.requests:
        q = r.query
        cols = {c for _, terms in q["aggregates"] for _, c in terms}
        cols |= {c for group in q["where"] for c, _, _ in group}
        cols |= set(q["groupby"])
        columns[r.query_text] = len(cols)
    total = 0.0
    for c in record.chunks:
        parts = len(set(int(i) for i in c.ids))
        total += parts * record.rows_per_partition * columns[c.query_text] * BYTES
        total += parts * c.raw.shape[1] * c.raw.shape[2] * BYTES
    return total


def read(record):
    if record.events is None:
        return None
    secs = trace.module_seconds(record.events, PROGRAMS)
    if secs <= 0:
        return None
    bandwidth = record.peaks[record.device_kind]["hbm_bytes_per_s"]
    return 100.0 * needed_bytes(record) / bandwidth / secs
