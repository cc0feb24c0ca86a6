"""Predictor: mean self time of the ``magnus.predict`` spans in the trace
(ms per request: the hashed n-gram embedding and the forest);
``bench/spans.py``."""
import spans as S


def read(ctx, name):
    return S.mean_self_ms(ctx, "magnus.predict")
