"""95th percentile, in milliseconds, of the time from when a request was
due to its first token as the client saw it, over every request due in
the window (one still waiting when the window closed counts the wait so
far).  Above the engine's capacity the queue grows through the window,
so this swings with small changes and is not judged (host clock)."""


def read(ctx):
    v = ctx["trace"].get("ttft_p95_s")
    return None if v is None else 1000.0 * v
