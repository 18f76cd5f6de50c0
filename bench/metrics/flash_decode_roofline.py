"""Share of its roofline that the decode-attention kernel reached in the
traced window, counting only the live cache: each token delivered in the
window was decoded at position p = prompt + index and needed K and V of
positions 0..p in every layer. Those bytes over HBM bandwidth, over the
kernel's summed device time. (The kernel reads every max_len block
today; counting what is needed keeps the yardstick fixed if that
changes.)"""
from bench import flops, trace as tr


def read(run):
    if run.trace is None:
        return None
    calls = tr.kernel_calls(run.trace["ops"], "flash_decode")
    busy = sum(e["dur_ns"] for e in calls) / 1e9
    if busy <= 0:
        return None
    (a, b), need = run.span, 0
    for r in run.rec.requests.values():
        P = r["prompt_len"]
        for j, t in enumerate(r["tokens"]):
            if a <= t <= b:
                need += flops.decode_attention_bytes(run.cfg, P + j)
    if need <= 0:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / busy
