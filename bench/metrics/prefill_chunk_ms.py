"""Model step: mean device milliseconds of one prefill-chunk program
(``jit_chunk_step``) executed in the traced window."""

from _common import usable


def read(ctx, name):
    if not usable(ctx):
        return None
    ts = ctx["trace"].module_times("jit_chunk_step")
    return 1e3 * sum(ts) / len(ts) if ts else None
