"""Kernels: roofline share of the paged prefill-attention kernel (%): the
roofline time of its work at each chunk's live context over the device
time of its events (``paged_prefill_attention``) in the traced window."""

from _common import flops, peaks, traced_ticks, usable


def read(ctx, name):
    if not usable(ctx):
        return None
    dev_s, n = ctx["trace"].op_time("paged_prefill_attention")
    if not n or dev_s <= 0:
        return None
    m, pk = ctx["model"], ctx["peaks"]
    need = sum(peaks.roofline_s(*flops.paged_prefill_kernel(m, *pre), pk)
               for _, _, _, pre in traced_ticks(ctx) if pre)
    return 100.0 * need / dev_s
