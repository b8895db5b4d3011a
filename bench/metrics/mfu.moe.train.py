"""The train step's model FLOP utilisation on a DeepSeek-V3-family cell,
in percent: the forward and backward FLOPs of every worker's tokens
through the held share, from shapes (``_work_deepseek_v3``, routed work
at its expected share), at the window's tokens per second, over the
chips' bfloat16 peak."""
from harness import common


def read(ctx):
    if ctx["cfg"].get("model_type") != "deepseek_v3":
        return None
    work = common.module("metrics", "_work_deepseek_v3")
    seq = ctx["mix"]["tokens_per_sequence"]
    per_token = work.train_flops(ctx["cfg"], seq) / seq
    tokens_per_s = ctx["e2e"]["train_tokens_per_s"][0]
    return 100.0 * per_token * tokens_per_s / (ctx["chips"]
                                               * ctx["peaks"].flops)
