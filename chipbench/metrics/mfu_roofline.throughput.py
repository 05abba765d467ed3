"""The whole step's share of the chip's roofline: the body's ideal time
per call times the calls in the traced window, over the window, in
percent."""

UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or t["calls"] <= 0:
        return None
    return 100.0 * ctx["ideal_s_per_call"] * t["calls"] / t["window_s"]
