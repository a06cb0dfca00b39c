"""Whole round: model FLOPs of the round's fit steps and eval forwards
over (round wall time x the cell's chips x peak bf16 FLOP/s), in
percent."""


def read(ctx):
    flops = (ctx.fit_flops_per_round + ctx.eval_flops_per_round) * ctx.rounds
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["bf16_flops"])
