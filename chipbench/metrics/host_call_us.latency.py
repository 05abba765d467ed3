"""Host microseconds from calling ``execute_network`` to its return,
before the sync: the harness loop's and the program's host path (memo
lookup, jit dispatch).  Median over the untraced part of the window."""
import statistics

UNIT = "us"


def read(ctx):
    calls = ctx["host"].get("host_call_s")
    return statistics.median(calls) * 1e6 if calls else None
