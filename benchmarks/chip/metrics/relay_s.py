"""FLARE relay: seconds per round in the program's ``repro.relay.request``
spans (a SuperNode's whole six-hop fleet call) that moved a task or a
result: pulls that found a task, and every result push."""
import program_spans as ps


def read(ctx):
    calls = ps.events(ctx, "repro.relay.request")
    if not calls or not ctx.rounds:
        return None
    return ps.duration_s(e for e in calls if ps.carried(e)) / ctx.rounds
