"""Share of its roofline that the fused dequant-matmul kernel reached in
the traced part of the window: for each call, the larger of 2*M*K*N over
the bf16 peak and its bytes (codes, activations, float32 result) over
HBM bandwidth, with M, K, N and the activations' dtype read from the
call's shapes; summed, over the kernel's summed device time."""
from bench import flops, trace as tr


def read(run):
    if run.trace is None:
        return None
    calls = tr.kernel_calls(run.trace["ops"], "dequant_matmul")
    if not calls:
        return None
    ideal = busy = 0.0
    for e in calls:
        sh = tr.shapes(e["name"])
        (xdt, (M, K)), (_, (_, N)) = sh[1], sh[2]
        ideal += flops.roofline_s(*flops.dequant_matmul_cost(M, K, N, tr.DTYPE_BYTES[xdt]),
                                  run.peaks)
        busy += e["dur_ns"] / 1e9
    return 100.0 * ideal / busy if busy > 0 else None
