"""Server fold kernel: the least time the fold could take over the device
time of the ``agg_*`` kernel calls, in percent.  The fold moves far more
bytes than it computes, so the bound is HBM bandwidth: the bytes of each
call's operands and results, from the shapes in its HLO, read or written
once at the peak rate."""
import flops
import trace_reduce as tr
from readings import NS


def read(ctx):
    ops = ctx.device_events("ops", lambda e: tr.op_name(e).startswith(
        "agg_"))
    if not ops:
        return None
    moved = sum(flops.hlo_shape_bytes(tr.op_shapes(e)) for e in ops)
    least = moved / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (sum(e.end - e.start for e in ops) * NS)
