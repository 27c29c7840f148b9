"""Host milliseconds per 10^6 configs handing chunks to the eval program:
the program's ``dse.fused.dispatch`` spans (each chunk's inputs gathered
and padded, the call enqueued, host to device).

Self time under each sweep job's ``dse.fused.sweep`` span over that job's
configs, the median over the jobs after the warm-up."""

import spans

SPANS = ("dse.fused.dispatch",)


def read(ctx):
    return spans.median_per_call(ctx.telemetry, "dse.fused.sweep", SPANS,
                                 lambda call: call["configs"] / 1e9)
