"""Device: percent of the traced window (whole rounds) in which no op
ran, averaged over the chips."""
import trace_reduce as tr


def read(ctx):
    lo, hi = ctx.window
    busy = tr.busy(ctx.trace, lo, hi)
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / (hi - lo))
