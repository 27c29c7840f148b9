"""Device time of the fused DSE eval program per 10^6 configs evaluated.

The eval is the per-chunk program of ``repro.dse.fused.FusedPipeline._fn``,
the jitted function ``fused``: its XLA module is ``jit_fused`` in the trace
(TPU v5 lite, jax 0.9.0)."""

MODULE = "jit_fused"


def read(ctx):
    s = ctx.trace.modules.get(MODULE)
    if not s:
        return None
    configs = sum(n for n, _ in ctx.job.families(ctx.traced_jobs))
    return 1e3 * s / (configs / 1e6)
