"""95th percentile of the gaps between consecutive tokens of a request,
as the client sees them: a token is visible at the flush that returns
it, so tokens of one flush are 0 s apart. Every gap in the window, of
every request."""
import numpy as np


def read(run):
    T = run.rec.window_s
    gaps = []
    for r in run.rec.requests.values():
        ts = [t for t in r["tokens"] if t <= T]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    return float(np.percentile(gaps, 95)) if gaps else None
