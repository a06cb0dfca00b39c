"""Wire codec: frame bytes produced or read over the time spent in the
program's ``repro.codec.encode`` and ``.decode`` spans, in GB/s."""
import program_spans as ps


def read(ctx):
    evs = ps.events(ctx, "repro.codec.encode", "repro.codec.decode")
    busy = ps.duration_s(evs)
    if not evs or busy <= 0:
        return None
    return sum(int(e.stats.get("nbytes", 0)) for e in evs) / busy / 1e9
