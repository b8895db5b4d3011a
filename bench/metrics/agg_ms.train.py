"""Device milliseconds per train step spent in the robust aggregation:
every op under the program's ``agg/`` scopes (distances, selection,
coordinate phase) and ``kernel/`` scopes (the fused kernels), from the
device trace.  Nothing when the trace names no such scope."""
from harness.trace import scope_seconds


def read(ctx):
    red = ctx["trace"]
    secs = scope_seconds(red, "agg", "kernel")
    if not secs:
        return None
    return 1000.0 * secs / red["steps"]
