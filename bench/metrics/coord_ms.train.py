"""Device milliseconds per train step in the robust aggregation's
coordinate phase: every op under the program's ``agg/coordinate`` scope
(the gather of the selected rows, Bulyan's per-coordinate sort and
window, the output casts), from the device trace.  Nothing when the
trace names no such scope."""
from harness.trace import scope_seconds


def read(ctx):
    red = ctx["trace"]
    secs = scope_seconds(red, "agg/coordinate")
    if not secs:
        return None
    return 1000.0 * secs / red["steps"]
