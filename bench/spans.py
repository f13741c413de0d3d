"""The program's own instrumentation, read out of a profiler trace.

The serving loop opens ``serve.*`` host spans (``jax.profiler
.TraceAnnotation``, in ``repro.serve.frontend`` and ``.scheduler``), and
the serving programs carry named scopes (``jax.named_scope`` in
``repro.models``) and named kernels (``tsmm_<leaf>``).  A scope reaches
the compiled program as each HLO instruction's ``op_name``; the trace
names a device op by its instruction, so :func:`scope_map` reads the
``op_name`` of every instruction from the program's HLO text
(``Compiled.as_text()``) and the op's self time goes to the innermost
scope in that path.  An op under ``layers`` outside the layer body's
``layer`` scope, or with no ``op_name``, is the layer scan's own:
per-layer slices of the stacked weights and cache, stacking of outputs,
copies XLA inserted.

Programs are known by the name they were lowered under
(``decode_step_b<slots>``, ``prefill_row_b<slots>_t<lb>``); a trace of
programs without those names, or without scopes, reads as nothing here.
Host spans are put on the device clock with ``trace.clock_offset``.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import Counter, defaultdict

from bench import trace as T

SERVE = "serve."
HARNESS = "bench."
SCAN = "scan"              # the layer scan's own ops
OTHER = "other"            # ops outside the layers and every scope
KERNELS = "kernels"        # tpu_custom_call ops, whatever their scope
OUTSIDE = "outside serve.tick"
LAYERS = "layers"
# scopes the serving programs open, whose ops are attributed to them
SCOPES = frozenset({
    "embed", "layer", "norm", "rope", "attention", "cache_write", "mlp",
    "head", "wq", "wk", "wv", "wqkv", "wo", "w_gate", "w_up", "w_down",
    "wq_a", "wq_b", "wkv_a", "wkv_b", "ws_gate", "ws_up", "ws_down"})
_MODULE = re.compile(r"^(?:jit_)?(?P<name>.*?)(?:\(\d+\))?$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+) = .*?"
                    r"metadata=\{op_name=\"(?P<op>[^\"]*)\"")


@dataclasses.dataclass
class Span:
    name: str
    start: float             # ns, device clock
    end: float
    args: dict


@dataclasses.dataclass
class Execution:
    program: str             # the name the program was lowered under
    start: float             # ns, device clock
    end: float
    ops: list                # (instruction, self ns, is a kernel)


@dataclasses.dataclass
class SpanTrace:
    window: tuple            # (start, end) ns of the traced window
    execs: list              # Execution, in start order, in the window
    spans: list              # Span of serve.* and bench.*, by start
    gaps: list               # (start, end) ns device-idle intervals
    scopes: dict             # program -> {instruction: op_name}

    def serve(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]


def program_of(module: str) -> str:
    """``jit_decode_step_b4(123)`` or ``HloModule`` name -> program."""
    return _MODULE.match(module).group("name")


def scope_map(hlo: str) -> tuple:
    """(program, {instruction name: op_name}) of one compiled program's
    HLO text."""
    head = hlo.split(",", 1)[0].split()
    out = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group("name")] = m.group("op")
    return program_of(head[-1]) if head else "", out


def scope_of(op_name: str) -> str:
    """Innermost named scope of an ``op_name`` path (a fused op's
    ``op_name`` joins its parts' paths with ``;``: the first path with a
    scope counts); else the scan's own op (under ``layers``, or with no
    ``op_name``) or an op outside both."""
    paths = [p.split("/") for p in op_name.split(";")] if op_name else []
    for parts in paths:
        for p in reversed(parts):
            if p in SCOPES:
                return p
    return SCAN if not paths or LAYERS in paths[0] else OTHER


def read(path: str, scopes: dict, *, device: int = 0) -> SpanTrace:
    """Read one trace file: program executions with their ops' self
    times, ``serve.*`` and ``bench.*`` host spans on the device clock,
    idle gaps, all inside the harness's ``bench.window`` span (or the
    whole trace).  ``scopes``: program -> {instruction: op_name}."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev = T._device_plane(pd, device)
    spans, executes = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith((SERVE, HARNESS)):
                    spans.append(Span(e.name, e.start_ns, e.end_ns,
                                      dict(e.stats)))
                elif e.name == T.EXECUTE:
                    executes.append(e.start_ns)
    execs = sorted((Execution(program_of(e.name), e.start_ns,
                              e.start_ns + e.duration_ns, [])
                    for e in T._line(dev, "XLA Modules")),
                   key=lambda x: x.start)
    offset = T.clock_offset([x.start for x in execs], executes)
    for s in spans:
        s.start += offset
        s.end += offset
    spans.sort(key=lambda s: s.start)
    marked = [s for s in spans if s.name == HARNESS + T.WINDOW]
    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
           for e in T._line(dev, "XLA Ops")]
    if marked:
        w0, w1 = marked[0].start, marked[0].end
    else:
        edges = [x for s, t, _ in ops for x in (s, t)] + [
            x for e in execs for x in (e.start, e.end)]
        w0, w1 = (min(edges), max(edges)) if edges else (0.0, 0.0)
    execs = [e for e in execs if e.end > w0 and e.start < w1]
    ops = [o for o in ops if o[0] < w1 and o[1] > w0]
    starts = [e.start for e in execs]
    for (s, t, name), own in zip(ops, T.self_times([o[:2] for o in ops])):
        i = _find(starts, s)
        if i is not None and s < execs[i].end:
            execs[i].ops.append((name.split(" = ", 1)[0].lstrip("%"), own,
                                 T.CUSTOM_CALL in name))
    _, merged = T.union_ns([(max(s, w0), min(t, w1)) for s, t, _ in ops])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return SpanTrace(window=(w0, w1), execs=execs,
                     spans=[s for s in spans if s.end > w0 and s.start < w1],
                     gaps=gaps, scopes=scopes)


def _find(starts, t):
    i = bisect.bisect_right(starts, t) - 1
    return i if i >= 0 else None


def executions(st: SpanTrace, prefix: str) -> list:
    return [e for e in st.execs if e.program.startswith(prefix)]


def split(st: SpanTrace, prefix: str):
    """(ns by scope, executions, program ns) summed over the executions
    of the programs named ``prefix...``: ``kernels`` for every
    tpu_custom_call op, the innermost scope for every other op.  None
    when those programs carry no scopes."""
    execs = executions(st, prefix)
    by = Counter()
    scoped = False
    for e in execs:
        names = st.scopes.get(e.program, {})
        for op, own, kernel in e.ops:
            label = scope_of(names.get(op, ""))
            scoped |= label not in (SCAN, OTHER)
            by[KERNELS if kernel else label] += own
    if not scoped:
        return None
    return dict(by), len(execs), sum(e.end - e.start for e in execs)


def decode_ms(st, label: str):
    """Device self time (ms) per ``decode_step`` execution of the ops
    labelled ``label`` by :func:`split`; None without a trace
    (``st`` None) or without scoped decode executions."""
    got = split(st, "decode_step") if st is not None else None
    if not got or not got[1]:
        return None
    by, n, _ = got
    return by.get(label, 0.0) / n * 1e-6


def kernel_leaves(st: SpanTrace, prefix: str) -> dict:
    """Kernel device time by the leaf scope it runs under, summed over
    the executions of the programs named ``prefix...``."""
    by = Counter()
    for e in executions(st, prefix):
        names = st.scopes.get(e.program, {})
        for op, own, kernel in e.ops:
            if kernel:
                by[scope_of(names.get(op, ""))] += own
    return dict(by)


def _children(parent: Span, spans: list, name: str) -> list:
    return [s for s in spans if s.name == name
            and parent.start <= s.start and s.end <= parent.end]


def host_gaps(st: SpanTrace) -> list:
    """Host time (ns) from the end of one decode step's
    ``serve.readback`` to the start of the next step's
    ``serve.dispatch``, for each pair of consecutive steps of one pool
    (``step`` args n and n + 1) with no ``serve.admit`` between them."""
    steps = st.serve(SERVE + "step")
    admits = [s.start for s in st.serve(SERVE + "admit")]
    reads = st.serve(SERVE + "readback")
    dispatches = st.serve(SERVE + "dispatch")
    out = []
    for a, b in zip(steps, steps[1:]):
        if b.args.get("step") != a.args.get("step", -2) + 1:
            continue
        if any(a.end <= t <= b.start for t in admits):
            continue
        ra = _children(a, reads, SERVE + "readback")
        db = _children(b, dispatches, SERVE + "dispatch")
        if ra and db:
            out.append(db[0].start - ra[-1].end)
    return out


def dispatch_lags(st: SpanTrace, prefix: str = "decode_step") -> tuple:
    """(lags, executions): for each execution of ``prefix...`` that
    starts on the device inside a ``serve.step`` span after its
    ``serve.dispatch`` began, the ns from that dispatch's start to the
    execution's start."""
    steps = st.serve(SERVE + "step")
    dispatches = st.serve(SERVE + "dispatch")
    starts = [s.start for s in steps]
    execs = executions(st, prefix)
    lags = []
    for e in execs:
        i = _find(starts, e.start)
        if i is None or e.start > steps[i].end:
            continue
        d = _children(steps[i], dispatches, SERVE + "dispatch")
        if d and d[0].start <= e.start:
            lags.append(e.start - d[0].start)
    return lags, len(execs)


def clock_check(st: SpanTrace, prefix: str = "decode_step") -> tuple:
    """(executions of ``prefix...`` that start on the device inside a
    ``serve.step`` span after its ``serve.dispatch`` began, executions):
    host spans and device ops on one clock put each step's program
    after its own dispatch."""
    lags, n = dispatch_lags(st, prefix)
    return len(lags), n


def span_means(st: SpanTrace) -> dict:
    """name -> (count, mean ns) of the ``serve.*`` host spans."""
    by = defaultdict(list)
    for s in st.spans:
        if s.name.startswith(SERVE):
            by[s.name].append(s.end - s.start)
    return {k: (len(v), sum(v) / len(v)) for k, v in by.items()}


def idle_by_program(st: SpanTrace) -> list:
    """[(where, idle seconds, gaps)], largest first: each idle gap by the
    program executing at its midpoint (a stall between its ops, named
    without its bucket suffix) or ``between programs``."""
    starts = [e.start for e in st.execs]
    totals, n = defaultdict(float), Counter()
    for g0, g1 in st.gaps:
        mid = 0.5 * (g0 + g1)
        i = _find(starts, mid)
        where = "between programs"
        if i is not None and mid < st.execs[i].end:
            where = "inside " + re.sub(r"_b\d+(_t\d+)?$", "",
                                       st.execs[i].program)
        totals[where] += (g1 - g0) * 1e-9
        n[where] += 1
    return sorted(((k, v, n[k]) for k, v in totals.items()),
                  key=lambda r: -r[1])


def _innermost(spans, t, prefix):
    best = None
    for s in spans:
        if s.start > t:
            break
        if (s.name.startswith(prefix) and s.name != HARNESS + T.WINDOW
                and s.start <= t <= s.end):
            best = s.name
    return best


def idle_labels(st: SpanTrace, state=None) -> list:
    """[(label, idle seconds, gaps)] over the idle gaps, largest first:
    the harness span open at each gap's midpoint, then the innermost
    ``serve.*`` span (or ``outside serve.tick``), then, where ``state``
    is given, what ``state(midpoint)`` says of the clients."""
    totals, n = defaultdict(float), Counter()
    for g0, g1 in st.gaps:
        mid = 0.5 * (g0 + g1)
        h = _innermost(st.spans, mid, HARNESS)
        label = (f"{h[len(HARNESS):] if h else 'no harness span open'}"
                 f" / {_innermost(st.spans, mid, SERVE) or OUTSIDE}")
        if state is not None:
            label = f"{label}, {state(mid)}"
        totals[label] += (g1 - g0) * 1e-9
        n[label] += 1
    return sorted(((k, v, n[k]) for k, v in totals.items()),
                  key=lambda r: -r[1])
