"""Engine: decode steps per fused decode window (one host readback each)
in the window."""
import readers as R


def read(ctx, name):
    ws = [w for w in ctx["probe"].windows if R.in_window(ctx, w[1])]
    return sum(w[2] for w in ws) / len(ws) if ws else None
