"""Model step: device time per ``decode_step`` execution in the trace."""

from bench.readers import programs


def read(ctx):
    d = programs(ctx, "decode_step")["total"]
    return d["ns"] / d["n"] * 1e-6 if d["n"] else None
