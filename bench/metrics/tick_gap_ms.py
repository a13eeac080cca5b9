"""Scheduler: mean device-idle milliseconds inside one ``bench.step`` span
(one ``BatchScheduler.step`` plus its ``flush``)."""

from _common import usable


def read(ctx, name):
    if not usable(ctx):
        return None
    idle = ctx["trace"].span_idle("bench.step")
    return 1e3 * sum(idle) / len(idle) if idle else None
