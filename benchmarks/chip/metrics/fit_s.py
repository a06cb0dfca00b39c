"""Client fit: seconds per round in ``bench.fit`` spans, summed over
sites (host<->device model copies, the local steps, the per-step loss
sync)."""


def read(ctx):
    return ctx.span_s_per_round("bench.fit")
