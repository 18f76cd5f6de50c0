"""Output tokens delivered to clients in the window, over the window."""


def read(run):
    T = run.rec.window_s
    n = sum(1 for r in run.rec.requests.values() for t in r["tokens"] if t <= T)
    return n / T if T > 0 else None
