"""Decode attention and cache (XLA): ``decode_step`` device time outside
its ``tpu_custom_call`` kernels, per execution."""

from bench.readers import programs


def read(ctx):
    d = programs(ctx, "decode_step")["total"]
    if not d["n"]:
        return None
    return (d["ns"] - d["custom_ns"]) / d["n"] * 1e-6
