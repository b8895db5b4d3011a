"""Device milliseconds per train step in the per-worker forward and
backward passes: ops under the program's ``train/grads`` scope; in a
trace of a program without that scope, ops under a differentiation
transform (``jvp(...)``, ``transpose(...)``), which is how it names the
same ops.  From the device trace; nothing when the trace names neither."""
import re

_GRADS = re.compile(r"(^|/)train/grads(/|$)")
_DIFF = re.compile(r"(^|/)(\w+\()*(jvp|transpose)\(")


def read(ctx):
    red = ctx["trace"]
    scopes = red["scope_s"]
    pat = _GRADS if any(_GRADS.search(k) for k in scopes) else _DIFF
    secs = sum(v for k, v in scopes.items() if pat.search(k))
    if not secs:
        return None
    return 1000.0 * secs / red["steps"]
