"""Client fit: seconds per round of model copies between host and
device, in the program's ``repro.xfer.h2d`` and ``repro.xfer.d2h`` spans,
summed over sites (fit and evaluate)."""
import program_spans as ps


def read(ctx):
    return ps.s_per_round(ctx, "repro.xfer.h2d", "repro.xfer.d2h")
