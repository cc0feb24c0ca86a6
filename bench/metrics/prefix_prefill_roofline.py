"""Kernels: the prefix-prefill attention kernel's least time on the chip
(FLOPs over peak or bytes over bandwidth, from each traced wave's suffix
and cached-prefix lengths) over its device time in the trace (%)."""
import flops as F
import readers as R


def read(ctx, name):
    if ctx["trace"] is None:
        return None
    m, peak = ctx["conf"], ctx["peak"]
    least = 0.0
    for _, rows in R.traced_waves(ctx):
        w = F.prefill_wave(m, rows)
        least += F.roofline_seconds(w["attn_flops"], w["attn_bytes"], peak)
    sec = R.kernel_seconds(ctx, R.PREFILL, least)
    return 100.0 * least / sec if least and sec else None
