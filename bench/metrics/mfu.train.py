"""The train step's model FLOP utilisation, in percent: the forward and
backward FLOPs of every worker's tokens, from shapes (``_work``), at the
window's tokens per second, over the chips' bfloat16 peak."""
from harness import common


def read(ctx):
    work = common.module("metrics", "_work")
    seq = ctx["mix"]["tokens_per_sequence"]
    per_token = work.train_flops(ctx["cfg"], seq) / seq
    tokens_per_s = ctx["e2e"]["train_tokens_per_s"][0]
    return 100.0 * per_token * tokens_per_s / (ctx["chips"]
                                               * ctx["peaks"].flops)
