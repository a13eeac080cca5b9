"""Scheduler: mean host milliseconds per traced tick of the scheduler's own
work: each ``repro.serve.tick`` span, and each ``repro.serve.flush`` span
outside a tick, less their ``repro.serve.{decode,prefill,readback}``
children (the dispatches' enqueue and the wait for the device)."""

import _spans

CHILDREN = ("repro.serve.decode", "repro.serve.prefill", "repro.serve.readback")


def read(ctx, name):
    got = _spans.program(ctx)
    if got is None:
        return None
    _, spans = got
    ticks = _spans.named(spans, "repro.serve.tick")
    if not ticks:
        return None
    tick_union = _spans.tr.union(ticks)
    own = [(a, b) for a, b in _spans.named(spans, "repro.serve.flush")
           if _spans.tr.covered(tick_union, a, b) < b - a]
    kids = [(a, b) for n, a, b in spans if n in CHILDREN]
    return 1e3 * sum(_spans.self_s(ticks + own, kids)) / len(ticks)
