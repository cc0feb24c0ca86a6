"""Model step: the admission waves' useful model FLOPs (suffix tokens
through every layer, attention over cached prefix and suffix, one logits
row per prompt) over the prefill programs' device time at the chip's
bf16 peak (%)."""
import flops as F
import readers as R


def read(ctx, name):
    if ctx["trace"] is None:
        return None
    work = sum(F.prefill_wave(ctx["conf"], rows)["model_flops"]
               for _, rows in R.traced_waves(ctx))
    sec = R.program_seconds(ctx, R.PREFILL, work)
    if not work or not sec:
        return None
    return 100.0 * work / (sec * ctx["peak"]["bf16_flops_per_s"])
