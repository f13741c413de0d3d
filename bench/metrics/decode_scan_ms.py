"""Decode attention and cache: device self time per ``decode_step``
execution of the layer scan's own ops (per-layer slices of the stacked
weights and cache, stacking of outputs, copies XLA inserted:
``bench/spans.py``), in ms.  Reads ``ctx.spans``; None where the context
has none or the program carries no named scopes."""

from bench import spans


def read(ctx):
    return spans.decode_ms(getattr(ctx, "spans", None), spans.SCAN)
