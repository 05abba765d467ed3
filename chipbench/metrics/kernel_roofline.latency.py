"""The body's ideal time per call (``body.Body.ideal_s_per_call``: the
larger of its FLOPs over peak FLOP/s and its bytes over peak HBM
bandwidth) over the device's busy time per call, in percent.  Busy time
counts every device operation, kernel or not."""

UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if not t or t["busy_s"] <= 0 or t["calls"] <= 0:
        return None
    return 100.0 * ctx["ideal_s_per_call"] * t["calls"] / t["busy_s"]
