"""SuperLink queues: seconds per round that tasks and results waited in
the SuperLink, summed over them: the ``queued_s`` of each task a relayed
pull returned (``repro.superlink.serve``, since the server pushed it)
and of each result handed to the server (``repro.superlink.deliver``,
since its push landed)."""
import program_spans as ps


def read(ctx):
    evs = ps.events(ctx, "repro.superlink.serve", "repro.superlink.deliver")
    if not evs or not ctx.rounds:
        return None
    return sum(float(e.stats.get("queued_s", 0.0)) for e in evs) / ctx.rounds
