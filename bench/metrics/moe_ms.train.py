"""Device milliseconds per train step in the expert layer: every op under
the program's ``moe/`` scopes (``moe/route``, ``moe/dispatch``,
``moe/experts``, ``moe/combine``) and the grouped matmul's kernels
(``_moe``), forward, backward and recomputation, from the device trace.
Nothing when the trace names no such op."""
from harness import common


def read(ctx):
    red = ctx["trace"]
    secs = common.module("metrics", "_moe").layer_seconds(red)
    if not secs:
        return None
    return 1000.0 * secs / red["steps"]
