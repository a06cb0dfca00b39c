"""FLARE relay: percent of the SuperNodes' relayed ``pull_task_ins``
calls (``repro.relay.request``) that found a task."""
import program_spans as ps


def read(ctx):
    pulls = [e for e in ps.events(ctx, "repro.relay.request")
             if ps.is_pull(e)]
    if not pulls:
        return None
    return 100.0 * sum(ps.carried(e) for e in pulls) / len(pulls)
