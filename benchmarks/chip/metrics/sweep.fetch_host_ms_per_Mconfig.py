"""Host milliseconds per 10^6 configs fetching the eval's results: the
program's ``dse.fused.fetch`` spans (the wait for the device, the copy to
host numpy and the emulated float64 unpack).

Self time under each sweep job's ``dse.fused.sweep`` span over that job's
configs, the median over the jobs after the warm-up."""

import spans

SPANS = ("dse.fused.fetch",)


def read(ctx):
    return spans.median_per_call(ctx.telemetry, "dse.fused.sweep", SPANS,
                                 lambda call: call["configs"] / 1e9)
