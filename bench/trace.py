"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy intervals, per-program device time, the
``tpu_custom_call`` (Pallas kernel) time inside each program, the
operations that took most time, and the idle gaps labelled by the host
span the harness had open.

Layout of a TPU trace as JAX 0.9 writes it: plane ``/device:TPU:<i>``
has a line ``XLA Modules`` (one event per program execution, named
``<module>(<id>)``) and a line ``XLA Ops`` (one event per HLO
instruction execution, named by its HLO text; a ``while`` event encloses
its body's events).  Host planes hold the harness's ``TraceAnnotation``
spans and ``PJRT_LoadedExecutable_Execute`` events.  Host and device
clocks in the file can differ by about a millisecond; ``clock_offset``
estimates the difference from the dispatch of each program.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import Counter, defaultdict
from typing import Optional

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
EXECUTE = "PJRT_LoadedExecutable_Execute"
WINDOW = "window"          # the harness's span around the traced window
_MODULE = re.compile(r"^(?P<name>.*)\(\d+\)$")
_OUT_SHAPE = re.compile(r"=\s*\(?\s*(\w+\[[\d,]*\])")


@dataclasses.dataclass
class Module:
    kind: str
    start: float            # ns, device clock
    end: float
    custom_ns: float = 0.0  # tpu_custom_call time inside


@dataclasses.dataclass
class Reduced:
    window: tuple           # (start, end) ns of the traced window
    busy_ns: float          # union of op intervals inside the window
    modules: list           # Module, in start order
    op_self_ns: dict        # "<kind> <op> <shape>" -> self time ns
    gaps: list              # (start, end) ns idle intervals in the window
    host_spans: list        # (name, start, end) ns, on the device clock
    offset_ns: float        # device clock minus host clock

    def by_kind(self) -> dict:
        """kind -> {"n": executions, "ns": device time, "custom_ns"}."""
        out = defaultdict(lambda: {"n": 0, "ns": 0.0, "custom_ns": 0.0})
        for m in self.modules:
            row = out[m.kind]
            row["n"] += 1
            row["ns"] += m.end - m.start
            row["custom_ns"] += m.custom_ns
        return dict(out)

    def idle_by_host(self, state=None) -> list:
        """[(label, idle seconds)] summed over the gaps, by the host span
        open at each gap's midpoint and, where ``state`` is given, by
        what ``state(midpoint)`` says of the clients then; largest
        first."""
        totals, counts = Counter(), Counter()
        for g0, g1 in self.gaps:
            mid = 0.5 * (g0 + g1)
            label = _innermost(self.host_spans, mid) or "no harness span open"
            if state is not None:
                label = f"{label}, {state(mid)}"
            totals[label] += (g1 - g0) * 1e-9
            counts[label] += 1
        return [(f"{k} ({counts[k]} gaps)", v) for k, v in
                totals.most_common()]


def _device_plane(pd, device: int = 0):
    for plane in pd.planes:
        if plane.name == f"/device:TPU:{device}":
            return plane
    raise ValueError(f"trace has no /device:TPU:{device} plane")


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return list(line.events)
    return []


def union_ns(intervals) -> tuple:
    """(total covered ns, merged intervals) of (start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [tuple(m) for m in merged]


def self_times(events) -> list:
    """Self time of nested (start, end) events: each event's duration
    less that of the events directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_ns = [e - s for s, e in events]
    stack = []
    for i in order:
        s, e = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return self_ns


def clock_offset(module_starts, execute_starts, tol_ns: float = 200e3):
    """Device-minus-host offset that puts most program starts just after
    a host dispatch: candidates from pairs of nearby events, scored by
    how many programs then start within ``tol_ns`` after a dispatch, and
    no program before its own."""
    if not module_starts or not execute_starts:
        return 0.0
    hs = sorted(execute_starts)
    cands = set()
    for m in module_starts[:20]:
        j = bisect.bisect_left(hs, m - 5e6)
        while j < len(hs) and hs[j] <= m + 5e6:
            cands.add(round(m - hs[j], -3))
            j += 1

    def score(off):
        n = 0
        for m in module_starts:
            j = bisect.bisect_right(hs, m - off) - 1
            if j >= 0 and (m - off) - hs[j] <= tol_ns:
                n += 1
        return n

    best = max(sorted(cands, key=abs), key=score, default=0.0)
    # a program starts after its dispatch: shift until the shortest
    # matched dispatch-to-start lag is zero
    lags = []
    for m in module_starts:
        j = bisect.bisect_right(hs, m - best) - 1
        if j >= 0 and (m - best) - hs[j] <= tol_ns:
            lags.append((m - best) - hs[j])
    return best + (min(lags) if lags else 0.0)


def _short_op(name: str) -> str:
    """``%fusion.12 = bf16[4,2560]{...} fusion(...)`` ->
    ``fusion.12 bf16[4,2560]`` (custom calls keep their kernel tag)."""
    head = name.split(" = ", 1)[0].lstrip("%")
    m = _OUT_SHAPE.search(name)
    shape = m.group(1) if m else ""
    tag = " tpu_custom_call" if CUSTOM_CALL in name else ""
    return f"{head} {shape}{tag}".strip()


def _innermost(spans, t):
    """Name of the innermost non-window span open at ``t``, or None."""
    best = None
    for name, s, e in spans:
        if s > t:
            break
        if name != WINDOW and s <= t <= e:
            best = name
    return best


def reduce(path: str, programs: tuple = (), *, host_prefix: str = "bench.",
           device: int = 0) -> Reduced:
    """Reduce one trace file.  A module whose name is in ``programs`` is
    named after the harness span open at its midpoint (a module's name
    alone does not tell the serving programs apart); any other module
    keeps its module name."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev = _device_plane(pd, device)
    mod_events = _line(dev, "XLA Modules")
    op_events = _line(dev, "XLA Ops")

    host_spans, executes = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(host_prefix):
                    host_spans.append((e.name[len(host_prefix):],
                                       e.start_ns, e.end_ns))
                elif e.name == EXECUTE:
                    executes.append(e.start_ns)

    modules = []
    for e in mod_events:
        m = _MODULE.match(e.name)
        modules.append(Module(kind=m.group("name") if m else e.name,
                              start=e.start_ns,
                              end=e.start_ns + e.duration_ns))
    modules.sort(key=lambda m: m.start)
    offset = clock_offset([m.start for m in modules], executes)
    host_spans = sorted(((n, s + offset, t + offset)
                         for n, s, t in host_spans), key=lambda x: x[1])
    for mod in modules:
        if mod.kind in programs:
            mod.kind = (_innermost(host_spans, 0.5 * (mod.start + mod.end))
                        or mod.kind)

    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
           for e in op_events]
    marked = [(s, t) for n, s, t in host_spans if n == WINDOW]
    if marked:
        window = marked[0]
    elif ops:
        window = (min([s for s, _, _ in ops] + [m.start for m in modules]),
                  max([t for _, t, _ in ops] + [m.end for m in modules]))
    else:
        window = (0.0, 0.0)
    w0, w1 = window
    modules = [m for m in modules if m.end > w0 and m.start < w1]
    ops = [(s, t, n) for s, t, n in ops if s < w1 and t > w0]
    busy, merged = union_ns([(max(s, w0), min(t, w1)) for s, t, _ in ops])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]

    mstarts = [m.start for m in modules]
    selfs = self_times([(s, t) for s, t, _ in ops])
    op_self = Counter()
    for (s, t, name), own in zip(ops, selfs):
        i = bisect.bisect_right(mstarts, s) - 1
        mod = modules[i] if i >= 0 and s < modules[i].end else None
        kind = mod.kind if mod else "outside programs"
        op_self[f"{kind} {_short_op(name)}"] += own
        if mod is not None and CUSTOM_CALL in name:
            mod.custom_ns += t - s
    return Reduced(window=window, busy_ns=busy, modules=modules,
                   op_self_ns=dict(op_self), gaps=gaps,
                   host_spans=host_spans, offset_ns=offset)
