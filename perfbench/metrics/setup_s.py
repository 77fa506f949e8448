"""Seconds from the process's start to the window's: imports, the chip's
start, the table, sketches, picker training, warm-up and compilation."""


def read(record):
    return record.setup["total_s"]
