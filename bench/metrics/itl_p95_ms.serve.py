"""95th percentile, in milliseconds, of every gap between consecutive
tokens of a request as the client saw them, over all requests in the
window; a step that admits requests stalls every running one (host
clock)."""


def read(ctx):
    v = ctx["trace"].get("itl_p95_s")
    return None if v is None else 1000.0 * v
