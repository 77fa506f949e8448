"""Programs JAX lowered inside the window (a new trace or shape: a
compile, or a fetch from the persistent cache).  Warm-up should leave 0."""


def read(record):
    return float(record.compiles_in_window)
