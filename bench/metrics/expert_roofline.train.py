"""The held experts' grouped matmul's share of its roofline, in percent:
the least time any implementation needs for one train step's expert
matmuls on the cell's chips, the larger of the bytes (the held weights
read forward and backward and their gradient written, per worker and
expert layer) over the HBM peak and the expected FLOPs over the compute
peak (``_work_deepseek_v3.expert_work``), divided by the device time per
step of the grouped matmuls: ops under the program's ``moe/experts``
scope and the grouped-matmul kernels (``_moe``).  Nothing when the trace
names no such op."""
from harness import common


def read(ctx):
    red = ctx["trace"]
    secs = common.module("metrics", "_moe").expert_seconds(red)
    if not secs or ctx["cfg"].get("model_type") != "deepseek_v3":
        return None
    work = common.module("metrics", "_work_deepseek_v3")
    mix = ctx["mix"]
    nbytes, flops = work.expert_work(
        ctx["cfg"], mix["workers"],
        mix["sequences_per_worker"] * mix["tokens_per_sequence"])
    pk, chips = ctx["peaks"], ctx["chips"]
    least = max(nbytes / (chips * pk.hbm_bw), flops / (chips * pk.flops))
    return 100.0 * least / (secs / red["steps"])
