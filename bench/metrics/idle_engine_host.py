"""Engine host (admission, waves, radix publish, grow, readback, retire):
share of the traced window in which the device is idle while the host is
inside an ``engine.*`` or ``radix.*`` span and no span nested in it (%);
``bench/spans.py``."""
import spans as S


def read(ctx, name):
    return S.idle_share(ctx, S.ENGINE_HOST)
