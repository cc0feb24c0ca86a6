"""Kernels: the paged decode attention kernel's least time on the chip
(the larger of its FLOPs over peak and the KV bytes it must read over the
HBM bandwidth, from the context lengths of each traced window) over its
device time in the trace (%)."""
import flops as F
import readers as R


def read(ctx, name):
    if ctx["trace"] is None:
        return None
    m, peak = ctx["conf"], ctx["peak"]
    least = 0.0
    for _, _, k, rows, c in R.traced_windows(ctx):
        w = F.decode_window(m, k, rows, c)
        least += F.roofline_seconds(w["attn_flops"], w["attn_bytes"], peak)
    sec = R.kernel_seconds(ctx, R.DECODE, least)
    return 100.0 * least / sec if least and sec else None
