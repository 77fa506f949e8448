"""Seconds of `Session.prepare`: the picker's training queries answered
exactly, the funnel fit and feature selection (the sketches are built
before it and timed apart)."""


def read(record):
    return record.setup.get("picker_train_s")
