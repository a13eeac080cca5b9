"""Device idle share: 1 - (union of operation intervals) / traced window,
mean over the cell's chips. In %."""

from _common import usable


def read(ctx, name):
    if not usable(ctx):
        return None
    t = ctx["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)
