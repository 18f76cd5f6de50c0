"""Median time to first token over every request due in the
window, timed from when it was due. A request with no token by the end
of the window counts with the time it has waited. The median, because a
45 s window at these rates holds 20 to 40 requests: no higher percentile
has ten of them beyond it."""
import numpy as np


def read(run):
    T = run.rec.window_s
    waits = []
    for r in run.rec.requests.values():
        if r["due"] > T:
            continue
        first = r["tokens"][0] if r["tokens"] else None
        waits.append((first if first is not None and first <= T else T) - r["due"])
    return float(np.percentile(waits, 50)) if waits else None
