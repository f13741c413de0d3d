"""Front end / scheduler: mean host time from the end of one decode
step's ``serve.readback`` to the start of the next step's
``serve.dispatch``, over consecutive steps of one pool with no
``serve.admit`` between them (``bench/spans.py``), in ms.  Reads
``ctx.spans`` (``bench.spans.read`` of the traced window); None where
the context has none or the trace holds no such pair."""

from bench import spans


def read(ctx):
    st = getattr(ctx, "spans", None)
    gaps = spans.host_gaps(st) if st is not None else []
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None
