"""Wire codec: seconds per round in the program's ``repro.codec.encode``
and ``.decode`` spans (every encode and decode of a message, and each
later model-size dequantize), summed over threads and sites."""
import program_spans as ps


def read(ctx):
    return ps.s_per_round(ctx, "repro.codec.encode", "repro.codec.decode")
