"""Share of the body's residual adds that the program leaves outside the
kernel, as a separate pass over HBM, in percent: its
``lowering.residual_separate`` counter over that plus
``lowering.residual_in_kernel`` (``repro.runtime.telemetry``, one per
chain with a residual, counted as the chain is traced).  Both count per
build, so the ratio is that of one build however often the body is
traced.  A program without the counters, or that counted no residual,
reads nothing."""

UNIT = "%"


def read(ctx):
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    counters = telemetry.runtime_report()["counters"]
    separate = counters.get("lowering.residual_separate", 0)
    total = separate + counters.get("lowering.residual_in_kernel", 0)
    return 100.0 * separate / total if total else None
