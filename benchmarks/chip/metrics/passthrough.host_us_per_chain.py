"""Host time per chain: over all chains of the traced window, the chain's
span less the device's busy time inside it, in us. This is what the
client driver, the server daemon's dispatch and the host side of the
copies cost a chain."""
import tracereduce

SPAN = "bench.chain"


def read(ctx):
    if ctx.trace is None:
        return None
    spans = tracereduce.span_durations(ctx.trace, SPAN)
    if not spans:
        return None
    busy = tracereduce.busy_within(ctx.trace, spans)
    host = sum(e - s for s, e in spans) - sum(busy)
    return host / len(spans) / 1e3
