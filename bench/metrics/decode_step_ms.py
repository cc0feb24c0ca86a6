"""Model step: device time of the fused decode programs in the trace per
decode step (ms)."""
import readers as R


def read(ctx, name):
    if ctx["trace"] is None:
        return None
    steps = sum(w[2] for w in R.traced_windows(ctx))
    sec = R.program_seconds(ctx, R.DECODE, steps)
    return sec / steps * 1e3 if steps and sec else None
