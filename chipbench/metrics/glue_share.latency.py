"""Share of the device's operation time spent in operations that are not
Pallas kernels (pads, slices, residual adds, casts), in percent."""

UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if not t or t["op_s"] <= 0:
        return None
    return 100.0 * (t["op_s"] - t["kernel_s"]) / t["op_s"]
