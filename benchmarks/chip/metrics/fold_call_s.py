"""Server fold: seconds per round in the program's ``repro.fold.kernel``
spans, the fold's device call between its staging and unstaging
(padding, the host-to-device and device-to-host copies, the kernel)."""
import program_spans as ps


def read(ctx):
    return ps.s_per_round(ctx, "repro.fold.kernel")
