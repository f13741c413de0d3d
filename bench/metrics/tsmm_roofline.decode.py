"""Kernels: least time of the decode step's kernel matmuls
(``bench/counts.py``: m = slots rows, each weight read once) over the
summed ``tpu_custom_call`` device time in ``decode_step``, in %."""

from bench import counts
from bench.readers import programs


def read(ctx):
    d = programs(ctx, "decode_step")["total"]
    if not d["custom_ns"]:
        return None
    calls = counts.step_matmuls(ctx.model, ctx.slots,
                                ctx.kernels["decode_step"])
    least, _ = counts.least_time(calls, ctx.peak)
    return 100.0 * d["n"] * least / (d["custom_ns"] * 1e-9)
