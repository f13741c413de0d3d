"""Model step: model FLOPs of the traced decode steps, live rows only
(each delivered token's matmuls and its attention over its context),
over their device time times the chip's peak, in %."""

from bench import counts
from bench.readers import decode_tokens, programs


def read(ctx):
    d = programs(ctx, "decode_step")["total"]
    if not d["ns"]:
        return None
    flops = sum(counts.token_flops(ctx.model, p + j)
                for p, j in decode_tokens(ctx))
    if not flops:
        return None
    return 100.0 * flops / (d["ns"] * 1e-9 * ctx.peak["bf16_flops_per_s"])
