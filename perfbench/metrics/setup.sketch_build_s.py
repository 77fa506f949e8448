"""Seconds of constructing the `repro` Session, which builds the per-
partition summary statistics (sketches) of every column."""


def read(record):
    return record.setup.get("sketch_build_s")
