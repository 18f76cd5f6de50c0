"""Device time in the engine's prefill-chunk executable (jit_chunk_step)
over device busy time, in the traced window."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    busy = tr.busy_s(run.trace["ops"])
    chunk = tr.module_s(run.trace["modules"], "chunk_step")
    if busy <= 0 or chunk <= 0:
        return None
    return 100.0 * chunk / busy
