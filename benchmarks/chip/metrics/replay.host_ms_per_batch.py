"""Host milliseconds per replay batch outside the scan: the self time of
the program's ``vt.arrivals`` (arrival traces), ``vt.draws`` (service
draws), ``vt.pack`` (group packing) and ``vt.percentiles`` (host
percentiles) spans under each ``VirtualTimeFabric.run_batch`` call's
``vt.batch`` span, the median over the calls after the warm-up."""

import spans

SPANS = ("vt.arrivals", "vt.draws", "vt.pack", "vt.percentiles")


def read(ctx):
    return spans.median_per_call(ctx.telemetry, "vt.batch", SPANS, lambda call: 1e-3)
