"""Model step: the decode work's model FLOPs (active rows only, counted
from the shapes by ``flops.decode_window``) over the decode programs'
device time at the chip's bf16 peak (%)."""
import flops as F
import readers as R


def read(ctx, name):
    if ctx["trace"] is None:
        return None
    m = ctx["conf"]
    work = sum(F.decode_window(m, k, rows, c)["model_flops"]
               for _, _, k, rows, c in R.traced_windows(ctx))
    sec = R.program_seconds(ctx, R.DECODE, work)
    if not work or not sec:
        return None
    return 100.0 * work / (sec * ctx["peak"]["bf16_flops_per_s"])
