"""Model step, whole, as a share of the chip's peak (%), for the hybrid
Mamba2/attention MoE model: the sum over every dispatch of the traced
window of its roofline time (the larger of useful FLOPs over peak FLOP/s
and useful bytes over peak HBM bytes/s; ``bench/flops_hybrid.py`` at the
live lengths), over the window, as ``mfu`` reads the dense model. The
routed experts' work counts the token-expert pairs that reached held
experts: the program's ``repro.serve.moe`` counters in the window give
the pairs a token, applied to each dispatch's tokens. A program without
those counters reads nothing."""

import _spans
import flops_hybrid
from _common import peaks, traced_ticks


def read(ctx, name):
    w = _spans.window(ctx)
    if w is None:
        return None
    moe = [a for t, a in _spans.arguments(ctx["out"]["trace_path"], "repro.serve.moe")
           if w[0] <= t <= w[1] and "moe_routed" in a and "tokens" in a]
    tokens = sum(a["tokens"] for a in moe)
    if not tokens:
        return None
    per_token = sum(a["moe_routed"] for a in moe) / tokens
    m, pk, f = ctx["model"], ctx["peaks"], flops_hybrid
    s = 0.0
    for _, _, lens, pre in traced_ticks(ctx):
        if lens:
            s += peaks.roofline_s(f.decode_flops(m, lens, per_token * len(lens)),
                                  f.decode_bytes(m, lens), pk)
        if pre:
            s += peaks.roofline_s(f.prefill_flops(m, *pre, per_token * pre[1]),
                                  f.prefill_bytes(m, *pre), pk)
    return 100.0 * s / ctx["trace"].window_s
