"""Decode attention and cache: device self time per ``decode_step``
execution of the ops under the ``attention`` scope (scores, mask,
softmax and values over the cache: ``bench/spans.py``), in ms.  Reads
``ctx.spans``; None where the context has none or the program carries no
named scopes."""

from bench import spans


def read(ctx):
    return spans.decode_ms(getattr(ctx, "spans", None), "attention")
