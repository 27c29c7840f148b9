"""Set-up seconds the program spends profiling the network: its own
``dse.capture`` (the quantized calibration forward) and ``dse.profile``
(deriving per-block cycle samples) telemetry spans."""

SPANS = ("dse.capture", "dse.profile")


def read(ctx):
    spans = [s for s in ctx.telemetry["spans"] if s["name"] in SPANS]
    if not spans:
        return None
    return sum(s["end"] - s["start"] for s in spans)
