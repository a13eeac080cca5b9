"""Experts: mean over the traced decode steps of the busiest held
expert's token-expert pairs over the mean held expert's,
``moe_busiest / (moe_routed / experts held)``, both summed over the
step's MoE layers (the program's ``repro.serve.moe`` counters); 1 is an
even load. A program without those counters reads nothing."""

import _spans


def read(ctx, name):
    w = _spans.window(ctx)
    if w is None:
        return None
    held = ctx["model"]["experts_held"][1]
    r = [a["moe_busiest"] / (a["moe_routed"] / held)
         for t, a in _spans.arguments(ctx["out"]["trace_path"], "repro.serve.moe")
         if w[0] <= t <= w[1] and a.get("dispatch") == "decode" and a.get("moe_routed")]
    return sum(r) / len(r) if r else None
