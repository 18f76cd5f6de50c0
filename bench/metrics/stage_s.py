"""Seconds per precision stage, from the window's start (the stream's
start) to the moment the engine applied stage k. With k the highest
stage applied by the window's end, at t_k: t_k / k once every stage is
in, else max(t_k / k, T / (k + 1)), so a stall after stage k still
shows and a stage landing just before the end makes no jump."""


def read(run):
    T = run.rec.window_s
    ups = [(t, s) for t, s in run.rec.upgrades if t <= T]
    if not ups:
        return T
    t_k, k = max(ups, key=lambda u: u[1])
    if k >= run.n_stages:
        return t_k / k
    return max(t_k / k, T / (k + 1))
