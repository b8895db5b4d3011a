"""Device milliseconds per train step in the robust rule's own
selection: ops under the program's ``agg/select`` scope (Krum's scores,
Bulyan's selection loop) that lie under neither ``agg/gram`` nor
``agg/coordinate`` (a program whose phases nest inside ``agg/select``
is read the same way), from the device trace.  Nothing when the trace
names no selection op."""
import re

_IN = re.compile(r"(^|/)agg/select(/|$)")
_OTHER = re.compile(r"(^|/)agg/(gram|coordinate)(/|$)")


def read(ctx):
    red = ctx["trace"]
    secs = sum(v for k, v in red["scope_s"].items()
               if _IN.search(k) and not _OTHER.search(k))
    if not secs:
        return None
    return 1000.0 * secs / red["steps"]
