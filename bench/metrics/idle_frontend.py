"""Magnus front end (predictor, batcher, scheduler): share of the traced
window in which the device is idle while the host is inside a
``magnus.*`` span and no span nested in it (%); ``bench/spans.py``."""
import spans as S


def read(ctx, name):
    return S.idle_share(ctx, S.FRONTEND)
