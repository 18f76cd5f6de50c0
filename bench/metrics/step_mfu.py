"""Model FLOPs of every prompt and output token processed in the traced
part of the window, over its length, over the chip's bf16 peak. A prompt
counts when its first token is delivered in that part; each delivered
token counts at its own position (it attends to every earlier one)."""
from bench import flops


def read(run):
    a, b = run.span
    if run.trace is None or b <= a:
        return None
    cfg, total = run.cfg, 0.0
    for r in run.rec.requests.values():
        P = r["prompt_len"]
        idx = [j for j, t in enumerate(r["tokens"]) if a <= t <= b]
        if not idx:
            continue
        if idx[0] == 0:
            total += flops.sequence_flops(cfg, 0, P)
        total += flops.sequence_flops(cfg, P + idx[0], P + idx[-1] + 1)
    if total <= 0:
        return None
    return 100.0 * total / (b - a) / run.peaks["bf16_flops_per_s"]
