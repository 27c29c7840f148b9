"""Device time of the virtual-time scan per simulated patch job.

The scan is the runner of ``repro.fabric.vtime.VirtualTimeFabric._jax_runner``
(``jax.jit(jax.vmap(one))``): its XLA module is ``jit_one`` in the trace
(TPU v5 lite, jax 0.9.0).  A patch job is one (design, request, patch) step
of the recurrence."""

MODULE = "jit_one"


def read(ctx):
    s = ctx.trace.modules.get(MODULE)
    if not s:
        return None
    return 1e9 * s / ctx.job.patch_jobs(ctx.traced_jobs)
