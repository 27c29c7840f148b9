"""Host milliseconds per replay batch in which the device waits on the
host around the scan: the self time of the program's ``vt.dispatch`` (bit
packing, runner lookup, enqueue) and ``vt.fetch`` (the wait for the scan,
the copy to host, the unpack) spans in the traced batches, less the
device's busy time in the traced window, per batch.

The device runs the replay's programs only inside these two spans, so what
is left is the host's own part of them.  The traced batches are the
``vt.batch`` calls that follow the first, the warm-up job, which the job
kind's constructor runs."""

import spans

SPANS = ("vt.dispatch", "vt.fetch")


def read(ctx):
    calls = spans.per_call(ctx.telemetry, "vt.batch")[1 : 1 + ctx.traced_jobs]
    if len(calls) < ctx.traced_jobs or not any(n in s for _, s in calls for n in SPANS):
        return None
    held = sum(s.get(n, 0.0) for _, s in calls for n in SPANS)
    return 1e3 * (held - ctx.trace.busy_s) / len(calls)
