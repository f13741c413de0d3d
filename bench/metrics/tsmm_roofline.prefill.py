"""Kernels: least time of ``prefill_row``'s kernel matmuls (m = the
length bucket's rows, each weight read once per admission) over the
summed ``tpu_custom_call`` device time in ``prefill_row``, in %."""

from bench import counts
from bench.readers import programs


def read(ctx):
    rows = programs(ctx, "prefill_row.")["rows"]
    least = custom = 0.0
    for name, row in rows.items():
        lb = int(name.split(".", 1)[1])
        calls = counts.step_matmuls(ctx.model, lb, ctx.kernels[name])
        least += row["n"] * counts.least_time(calls, ctx.peak)[0]
        custom += row["custom_ns"] * 1e-9
    return 100.0 * least / custom if custom else None
