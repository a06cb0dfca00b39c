"""Server fold: seconds per round in ``bench.fold`` spans (the
accumulator's ``add`` calls and its ``finalize``)."""


def read(ctx):
    return ctx.span_s_per_round("bench.fold")
