"""Model step: model FLOPs of the real prompt tokens admitted in the
trace (causal attention counted once) over ``prefill_row`` device time
times the chip's peak, in %."""

from bench import counts
from bench.readers import admissions, programs


def read(ctx):
    p = programs(ctx, "prefill_row")["total"]
    lens = admissions(ctx)
    if not p["ns"] or not lens:
        return None
    flops = sum(counts.prompt_flops(ctx.model, n) for n in lens)
    return 100.0 * flops / (p["ns"] * 1e-9 * ctx.peak["bf16_flops_per_s"])
