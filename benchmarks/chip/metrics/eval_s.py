"""Client evaluate: seconds per round in ``bench.eval`` spans, summed
over sites."""


def read(ctx):
    return ctx.span_s_per_round("bench.eval")
