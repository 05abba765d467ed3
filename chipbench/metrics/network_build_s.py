"""Seconds the program spent building its network in this process: its
``network.build_ns`` counter (``repro.runtime.telemetry``), summed over
``execute_network``'s memo misses (plan, jit, compile or cache load,
first call).  Part of ``setup_s``; a program without the counter reads
nothing."""

UNIT = "s"


def read(ctx):
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    ns = telemetry.runtime_report()["counters"].get("network.build_ns")
    return ns * 1e-9 if ns else None
