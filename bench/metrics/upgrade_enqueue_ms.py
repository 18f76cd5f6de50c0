"""Mean host milliseconds the engine's upgrade_if_available spent
enqueueing a precision upgrade (its upgrade_log), over the upgrades in
the traced part of the window."""


def read(run):
    a, b = run.span
    ups = run.rec.upgrades
    if not ups:
        return None
    log = run.upgrade_log[-len(ups):]     # the window's own upgrades, in order
    sel = [u["enqueue_s"] for u, (t, _) in zip(log, ups) if a <= t <= b]
    return 1e3 * sum(sel) / len(sel) if sel else None
