"""Robust ensemble serving's model FLOP utilisation, in percent: the
forward FLOPs, from shapes (``_work``), of every replica's prefill of
each admitted prompt and of each decoded token against its context, for
the tokens clients saw in the window, over the window and the chips'
bfloat16 peak."""


def read(ctx):
    red = ctx["trace"]
    flops, window = red.get("model_flops"), red.get("window_s_e2e")
    if not flops or not window:
        return None
    return 100.0 * flops / (window * ctx["chips"] * ctx["peaks"].flops)
