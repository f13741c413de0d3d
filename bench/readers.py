"""Helpers the per-layer metric readers share (``bench/metrics/*.py``).

Each reader takes a ``bench.run.MetricContext`` and returns a number or
None when its cell has nothing for it to read; a share of a roofline or
of a peak is never made up as 0.
"""

from __future__ import annotations


def programs(ctx, prefix: str) -> dict:
    """Summed device time of the traced programs whose name starts with
    ``prefix``: {"n", "ns", "custom_ns"} and the per-name rows."""
    rows = {k: v for k, v in ctx.reduced.by_kind().items()
            if k.startswith(prefix)}
    total = {"n": sum(v["n"] for v in rows.values()),
             "ns": sum(v["ns"] for v in rows.values()),
             "custom_ns": sum(v["custom_ns"] for v in rows.values())}
    return {"total": total, "rows": rows}


def decode_tokens(ctx):
    """(prompt length, index) of every token a decode step produced that
    the client received inside the traced window (index >= 1: token 0
    comes from the admission's prefill)."""
    t0, t1 = ctx.trace_window
    for r in ctx.records:
        p = len(r.req.prompt)
        for j, t in enumerate(r.times):
            if j >= 1 and t0 <= t < t1:
                yield p, j


def admissions(ctx):
    """Prompt lengths of the requests whose first token (from
    ``prefill_row``) the client received inside the traced window."""
    t0, t1 = ctx.trace_window
    return [len(r.req.prompt) for r in ctx.records
            if r.times and t0 <= r.times[0] < t1]
