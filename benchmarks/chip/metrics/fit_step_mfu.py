"""Client fit step: model FLOPs of the train steps over (their summed
device time x peak bf16 FLOP/s), in percent.  A train step runs on every
chip of the cell (a (data=chips) mesh), one ``train_step`` program event
on each, so the steps are the events over ``chips`` and their time the
events' summed device time: the share of the chips' summed peak."""
from readings import NS


def read(ctx):
    steps = ctx.device_events("modules", lambda e: "train_step" in e.name)
    if not steps:
        return None
    per_step = ctx.fit_flops_per_round / (ctx.mix["sites"]
                                          * ctx.mix["local_steps"])
    busy = sum(e.end - e.start for e in steps) * NS
    return (100.0 * per_step * len(steps) / ctx.chips
            / (busy * ctx.peaks["bf16_flops"]))
