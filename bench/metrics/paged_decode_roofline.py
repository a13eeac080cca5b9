"""Kernels: roofline share of the paged decode-attention kernel (%): the
roofline time of its work at the live context lengths over the device
time of its events (``paged_attention``) in the traced window."""

from _common import flops, peaks, traced_ticks, usable


def read(ctx, name):
    if not usable(ctx):
        return None
    dev_s, n = ctx["trace"].op_time("paged_attention")
    if not n or dev_s <= 0:
        return None
    m, pk = ctx["model"], ctx["peaks"]
    need = sum(peaks.roofline_s(*flops.paged_decode_kernel(m, lens), pk)
               for _, _, lens, _ in traced_ticks(ctx) if lens)
    return 100.0 * need / dev_s
