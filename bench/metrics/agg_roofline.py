"""The robust aggregation's share of its roofline, in percent: the least
time any implementation needs on the cell's chips, the larger of one
read of the (n, d) float32 gradient stack plus one float32 write of the
aggregate over the HBM peak and the Gram matrix's FLOPs over the compute
peak (``_work.agg_work``), divided by the measured ``agg_ms.train``."""
from harness import common


def read(ctx):
    ms = common.module("metrics", "agg_ms.train").read(ctx)
    if not ms:
        return None
    work = common.module("metrics", "_work")
    nbytes, flops = work.agg_work(ctx["cfg"], ctx["mix"]["workers"])
    pk, chips = ctx["peaks"], ctx["chips"]
    least = max(nbytes / (chips * pk.hbm_bw), flops / (chips * pk.flops))
    return 100.0 * least / (ms / 1000.0)
