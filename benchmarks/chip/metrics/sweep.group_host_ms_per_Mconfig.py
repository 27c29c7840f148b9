"""Host milliseconds per 10^6 configs spent grouping the sweep's points:
the program's ``dse.fused.group`` spans, ``run_fused_sweep``'s loop that
keys every point by its geometry and each group's ADC, policy and PE
arrays.

Self time (a span's own, less what its children cover) under each sweep
job's ``dse.fused.sweep`` span over that job's configs, the median over
the jobs after the warm-up."""

import spans

SPANS = ("dse.fused.group",)


def read(ctx):
    return spans.median_per_call(ctx.telemetry, "dse.fused.sweep", SPANS,
                                 lambda call: call["configs"] / 1e9)
