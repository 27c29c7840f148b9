"""Share of the traced sweep window in which the device was idle and no
program span was open on the harness's host thread: idle time that the
program's spans leave unnamed (``spans.idle_by_span``), over the window.

It reads the trace again, so it is kept to the sweep, whose trace is small.
A program without span annotations gives nothing to read."""

import spans


def read(ctx):
    path = spans.trace_file()
    if path is None or ctx.trace.window_s <= 0:
        return None
    idle = spans.idle_by_span(ctx.trace, path)
    if idle is None:
        return None
    return 100.0 * idle.get(spans.UNATTRIBUTED, 0.0) / ctx.trace.window_s
