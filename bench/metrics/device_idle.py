"""Device: share of the traced window in which no operation ran on the
chip (%): 1 - union of device-op intervals / window."""


def read(ctx, name):
    red = ctx["trace"]
    if red is None or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
