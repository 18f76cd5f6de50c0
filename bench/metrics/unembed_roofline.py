"""Share of its roofline that the untied unembedding reached in the
traced part of the window: the ``dequant_matmul`` calls whose code
operand is K x N with N the configuration's vocabulary (the head, found
by its shape), each at the larger of 2*M*K*N over the bf16 peak and its
bytes (codes, activations, float32 logits) over HBM bandwidth; summed,
over those calls' summed device time."""
from bench import flops, trace as tr


def read(run):
    if run.trace is None:
        return None
    ideal = busy = 0.0
    for e in tr.kernel_calls(run.trace["ops"], "dequant_matmul"):
        sh = tr.shapes(e["name"])
        (xdt, (M, K)), (_, (_, N)) = sh[1], sh[2]
        if N != run.cfg["vocab"]:
            continue
        ideal += flops.roofline_s(*flops.dequant_matmul_cost(M, K, N, tr.DTYPE_BYTES[xdt]),
                                  run.peaks)
        busy += e["dur_ns"] / 1e9
    return 100.0 * ideal / busy if busy > 0 else None
