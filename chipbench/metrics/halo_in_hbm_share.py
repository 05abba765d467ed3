"""Share of the body's SAME halos that the program pads in HBM ahead of a
kernel, in percent: its ``lowering.halo_padded`` counter over that plus
``lowering.halo_in_kernel`` (``repro.runtime.telemetry``, counted as each
segment is traced).  Both count per build, so the ratio is that of one
build however often the body is traced.  A program without the counters,
or that counted no halo, reads nothing."""

UNIT = "%"


def read(ctx):
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    counters = telemetry.runtime_report()["counters"]
    padded = counters.get("lowering.halo_padded", 0)
    total = padded + counters.get("lowering.halo_in_kernel", 0)
    return 100.0 * padded / total if total else None
