"""Host milliseconds per 10^6 configs of allocation: the program's
``dse.fused.allocate`` spans in ``FusedPipeline.__call__`` (validation,
proportional replicas and the greedy event-schedule lookups).

Self time under each sweep job's ``dse.fused.sweep`` span over that job's
configs, the median over the jobs after the warm-up."""

import spans

SPANS = ("dse.fused.allocate",)


def read(ctx):
    return spans.median_per_call(ctx.telemetry, "dse.fused.sweep", SPANS,
                                 lambda call: call["configs"] / 1e9)
