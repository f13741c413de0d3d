"""Model step: ``prefill_row`` device time per 1000 real prompt tokens
admitted in the trace (padding to the length bucket counts against it)."""

from bench.readers import admissions, programs


def read(ctx):
    p = programs(ctx, "prefill_row")["total"]
    tokens = sum(admissions(ctx))
    if not p["ns"] or not tokens:
        return None
    return p["ns"] * 1e-6 / (tokens / 1000.0)
