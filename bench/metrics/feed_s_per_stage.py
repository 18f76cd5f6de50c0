"""Host seconds spent inside ProgressiveClient.feed in the traced part of
the window (the benchmark's span around each call), per precision stage
the engine applied in it."""


def read(run):
    a, b = run.span
    ups = [s for t, s in run.rec.upgrades if a <= t <= b]
    before = max((s for t, s in run.rec.upgrades if t < a), default=0)
    stages = max(ups, default=before) - before
    feeds = [t1 - t0 for t0, t1, _ in run.rec.feeds if a <= t0 and t1 <= b]
    if stages <= 0 or not feeds:
        return None
    return sum(feeds) / stages
