"""Server fold: seconds per round of host staging around the fold
kernel, in the program's ``repro.fold.stage`` (stacking the arrivals and
the fp64 delta base) and ``repro.fold.unstage`` (the base add and the
cast to the new model) spans."""
import program_spans as ps


def read(ctx):
    return ps.s_per_round(ctx, "repro.fold.stage", "repro.fold.unstage")
