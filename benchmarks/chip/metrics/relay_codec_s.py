"""Wire codec, FLARE relay and SuperLink: seconds per round of the
window that no fit, evaluate or fold span covers (the residual until the
program has spans of its own there)."""
import trace_reduce as tr
from readings import NS


def read(ctx):
    lo, hi = ctx.window
    covered = tr.length(tr.clip(
        ((e.start, e.end) for n in ("bench.fit", "bench.eval", "bench.fold")
         for e in ctx.spans(n)), lo, hi))
    return ((hi - lo) - covered) * NS / ctx.rounds
