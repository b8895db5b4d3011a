"""Mean host milliseconds of one ``ServingEngine.admit`` in the window:
robust prefill on every replica, the cache splice and the first token,
which ends in a device sync (host clock around the public call)."""


def read(ctx):
    return ctx["trace"].get("admit_ms")
