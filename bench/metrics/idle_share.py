"""Share of the traced window in which no operation ran on the device."""
from bench import trace as tr


def read(run):
    a, b = run.span
    if run.trace is None or not run.trace["ops"] or b <= a:
        return None
    return 100.0 * (1.0 - tr.busy_s(run.trace["ops"]) / (b - a))
