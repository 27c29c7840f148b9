"""The fused DSE eval program's share of its roofline.

Bound by bytes: the least HBM traffic of the configs evaluated
(``counts.eval_least_bytes``) at the chip's HBM bandwidth, over the device
time of the eval's XLA module (``jit_fused``).  Its operations (a few dozen
per layer and config) lie far below the compute peak's share of that time."""

import counts

MODULE = "jit_fused"


def read(ctx):
    s = ctx.trace.modules.get(MODULE)
    if not s:
        return None
    layers = len(ctx.config["layers"])
    least = sum(counts.eval_least_bytes(n, r, layers) for n, r in ctx.job.families(ctx.traced_jobs))
    return 100.0 * (least / ctx.peaks["hbm_bytes_per_s"]) / s
