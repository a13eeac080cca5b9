"""Model step, whole, as a share of the chip's peak (%).

Serving: the sum over every dispatch of the traced window of its roofline
time (the larger of useful FLOPs over peak FLOP/s and useful bytes over
peak HBM bytes/s; bench/flops.py), over the window. Training: useful
FLOPs per step (no recomputation) times the steps of the window, over the
window, the chips and the peak FLOP/s."""

from _common import flops, peaks, traced_steps, traced_ticks, usable


def read(ctx, name):
    if not usable(ctx):
        return None
    t, pk, m = ctx["trace"], ctx["peaks"], ctx["model"]
    if ctx["cell"].workload["driver"] == "train":
        n = traced_steps(ctx)
        tr = ctx["cell"].traffic
        per_step = flops.train_flops_per_token(m) * tr["global_batch"] * tr["seq_len"]
        return 100.0 * per_step * n / t.window_s / (ctx["cell"].chips * pk.flops) if n else None
    ticks = traced_ticks(ctx)
    if not ticks:
        return None
    s = 0.0
    for _, _, lens, pre in ticks:
        if lens:
            s += peaks.roofline_s(flops.decode_flops(m, lens), flops.decode_bytes(m, lens), pk)
        if pre:
            s += peaks.roofline_s(flops.prefill_flops(m, *pre), flops.prefill_bytes(m, *pre), pk)
    return 100.0 * s / t.window_s
