"""Scheduler: mean over the traced ticks of the prompt tokens not yet
prefilled at the tick's start (the ``backlog_tok`` argument of each
``repro.serve.tick`` span), over queued, parked and prefilling requests.
One chunk a tick works it off; what waits behind it is the first-token
wait."""

import _spans


def read(ctx, name):
    w = _spans.window(ctx)
    if w is None:
        return None
    ticks = [a["backlog_tok"] for t, a in _spans.arguments(ctx["out"]["trace_path"], "repro.serve.tick")
             if w[0] <= t <= w[1] and "backlog_tok" in a]
    return sum(ticks) / len(ticks) if ticks else None
