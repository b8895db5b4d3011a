"""Device milliseconds per call of the robust decode program (the jitted
``make_robust_serve_step`` of the engine), from the device trace: the
mean duration of its executions in the traced window."""


def read(ctx):
    calls = ctx["trace"].get("module_calls", {}).get("jit_serve_step")
    if not calls:
        return None
    return 1000.0 * sum(calls) / len(calls)
