"""Share of its roofline that the eq.-(4) plane OR kernel reached in the
traced part of the window: each call reads the accumulator and the plane
and writes the accumulator (its element count read from the call's
result shape), bound by HBM bandwidth; summed over calls, over the
kernel's summed device time."""
from bench import flops, trace as tr


def read(run):
    if run.trace is None:
        return None
    calls = tr.kernel_calls(run.trace["ops"], "plane_or_segments")
    if not calls:
        return None
    nbytes = 0
    for e in calls:
        _, dims = tr.shapes(e["name"])[0]
        n = 1
        for d in dims:
            n *= d
        nbytes += flops.plane_or_bytes(n)
    busy = sum(e["dur_ns"] for e in calls) / 1e9
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / busy if busy > 0 else None
