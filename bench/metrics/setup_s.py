"""Seconds from the process's start to the window's opening: imports,
weights, divide, encode, ingest where the cell needs full precision, and
warm-up (compilation, or loading from the persistent cache)."""


def read(run):
    return run.setup_s
