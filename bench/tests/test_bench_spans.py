"""Reading the program's own spans and scopes (``bench/spans.py``): the
rules on synthetic spans and ``op_name`` paths, the scope map of a tiny
model's compiled ``decode_step``, and the three readers on a serving
trace recorded on a TPU v5e (``bench/tools/record_serve_fixture.py``: a
two-layer model served through ``AsyncEngine``, six requests, inside the
harness's window span)."""

import json
import types
from pathlib import Path

import pytest

from bench import run as R
from bench import spans
from bench.spans import Execution, Span, SpanTrace

DATA = Path(__file__).resolve().parent / "data"
FIXTURE = DATA / "serve_fixture.xplane.pb"
SCOPES = DATA / "serve_fixture_scopes.json"
READERS = ("host_gap_ms", "decode_scan_ms", "decode_attention_ms")
LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "head")


@pytest.mark.parametrize("op_name, label", [
    ("jit(decode_step_b4)/layers/while/body/closed_call/layer/mlp/w_gate/"
     "jit(_skinny_compute)/tsmm_w_gate/pallas_call", "w_gate"),
    # latent attention's and the shared experts' leaves are scopes too
    ("jit(decode_step_b4)/layers/while/body/closed_call/layer/wkv_a/"
     "jit(_skinny_compute)/tsmm_wkv_a/pallas_call", "wkv_a"),
    ("jit(decode_step_b4)/layers/while/body/closed_call/layer/ws_down/"
     "dot_general", "ws_down"),
    ("jit(decode_step_b4)/layers/while/body/closed_call/layer/attention/"
     "bhgd,bkhd->bhgk/dot_general", "attention"),
    ("jit(decode_step_b4)/layers/while/body/dynamic_slice", spans.SCAN),
    ("jit(decode_step_b4)/layers/while/body/squeeze", spans.SCAN),
    ("", spans.SCAN),
    ("jit(decode_step_b4)/add", spans.OTHER),
    ("jit(decode_step_b4)/layers/while/body/closed_call/layer/add",
     "layer"),
    ("jit(decode_step_b4)/head/reshape;jit(decode_step_b4)/head/reshape",
     "head"),
    # a fusion's op_name: the first of its paths that has a scope counts
    ("jit(f)/layers/while/body/squeeze;jit(f)/layers/while/body/"
     "closed_call/layer/cache_write/dynamic_update_slice", "cache_write"),
])
def test_scope_of_takes_the_innermost_scope(op_name, label):
    assert spans.scope_of(op_name) == label


def test_program_named_from_module_or_hlo_header():
    assert spans.program_of("jit_decode_step_b4(1234)") == "decode_step_b4"
    assert spans.program_of("jit_prefill_row_b16_t512") == \
        "prefill_row_b16_t512"
    hlo = ("HloModule jit_decode_step_b2, is_scheduled=true\n"
           "  %fusion.3 = f32[2]{0} fusion(%p), kind=kLoop, "
           "metadata={op_name=\"jit(decode_step_b2)/layers/while/body/"
           "dynamic_slice\" stack_frame_id=2}\n"
           "  ROOT %tsmm_wo.1 = bf16[2,8]{1,0} custom-call(%a), "
           "metadata={op_name=\"jit(decode_step_b2)/layers/wo/"
           "pallas_call\"}\n"
           "  %p = f32[2]{0} parameter(0)\n")
    name, ops = spans.scope_map(hlo)
    assert name == "decode_step_b2"
    assert ops == {"fusion.3": "jit(decode_step_b2)/layers/while/body/"
                               "dynamic_slice",
                   "tsmm_wo.1": "jit(decode_step_b2)/layers/wo/pallas_call"}


def test_scope_map_of_a_tiny_compiled_decode_step():
    """The cache einsum sits under ``attention``, every packed leaf's
    matmul under its leaf, and the scan's per-layer slices under no
    scope of the layer body."""
    import jax
    import numpy as np
    from repro.configs import get_reduced_config
    from repro.models.registry import build_model
    from repro.serve.engine import Engine
    from repro.serve.scheduler import Request

    cfg = get_reduced_config("qwen1_5_4b").reduced(
        d_model=256, d_ff=512, num_layers=2, vocab_size=512,
        num_heads=4, num_kv_heads=4, head_dim=64)
    model = build_model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, axes, max_len=64, max_batch=2,
                 max_prompt=16)
    eng.serve_queue([Request(tokens=np.arange(5, dtype=np.int32),
                             max_new_tokens=3)])
    (prog,) = [p for p in eng.programs.handles() if p.kind == "decode"]
    name, ops = spans.scope_map(prog.executable.as_text())
    assert name == f"decode_step_b{eng.buckets[-1]}"
    labels = {op: spans.scope_of(path) for op, path in ops.items()}
    dots = {labels[op] for op, path in ops.items() if "dot_general" in path}
    assert "attention" in dots
    assert set(LEAVES) <= dots
    cache_dots = [op for op, path in ops.items()
                  if "/attention/" in path and "dot_general" in path]
    assert cache_dots and all(labels[op] == "attention" for op in cache_dots)
    slices = [op for op, path in ops.items()
              if path.endswith("/while/body/dynamic_slice")]
    assert slices and all(labels[op] == spans.SCAN for op in slices)


def _trace(spans_, execs=(), gaps=(), scopes=None):
    return SpanTrace(window=(0.0, 1e9), execs=list(execs),
                     spans=sorted(spans_, key=lambda s: s.start),
                     gaps=list(gaps), scopes=scopes or {})


def _step(n, t, *, dispatch=10, readback=(30, 40), end=50, live=4):
    """A ``serve.step`` at ``t`` (ns offsets inside it)."""
    return [Span("serve.step", t, t + end, {"step": n, "live": live}),
            Span("serve.dispatch", t + dispatch, t + dispatch + 5, {}),
            Span("serve.readback", t + readback[0], t + readback[1], {})]


def test_host_gaps_pair_steps_of_one_pool_without_admissions():
    sp = (_step(0, 0) + _step(1, 100) + _step(2, 200)
          + [Span("serve.admit", 260, 280, {"rid": 7})]
          + _step(3, 300) + _step(0, 400) + _step(1, 500))
    st = _trace(sp)
    # 0->1, 1->2 and the next pool's 0->1 (readback end to dispatch:
    # 40 -> 110); 2->3 has an admission between, 3->0 opens a new pool
    assert spans.host_gaps(st) == [70, 70, 70]


def test_clock_check_counts_programs_after_their_own_dispatch():
    sp = _step(0, 0) + _step(1, 100) + _step(2, 200)
    execs = [Execution("decode_step_b4", 12, 28, []),   # after dispatch
             Execution("decode_step_b4", 105, 128, []),  # before it
             Execution("decode_step_b4", 260, 270, []),  # outside a step
             Execution("prefill_row_b4_t64", 215, 220, [])]
    assert spans.clock_check(_trace(sp, execs)) == (1, 3)
    assert spans.dispatch_lags(_trace(sp, execs)) == ([2], 3)


def test_idle_labels_name_the_innermost_serve_span():
    sp = ([Span("serve.tick", 0, 100, {}),
           Span("bench.decode_step", 5, 60, {})] + _step(0, 5)
          + [Span("serve.deliver", 70, 80, {})])
    gaps = [(36, 38), (72, 74), (150, 160)]
    labels = {k: (v, n) for k, v, n in spans.idle_labels(
        _trace(sp, gaps=gaps), lambda t: "requests running")}
    assert set(labels) == {
        "decode_step / serve.readback, requests running",
        "no harness span open / serve.deliver, requests running",
        f"no harness span open / {spans.OUTSIDE}, requests running"}
    assert labels["decode_step / serve.readback, requests running"] == (
        pytest.approx(2e-9), 1)


def test_readers_read_nothing_without_spans_or_scopes():
    bare = types.SimpleNamespace()
    unscoped = types.SimpleNamespace(spans=_trace(
        _step(0, 0), [Execution("decode_step_b4", 12, 28,
                                [("fusion.1", 16, False)])],
        scopes={"decode_step_b4": {"fusion.1": "jit(f)/while/body/add"}}))
    for name in READERS:
        reader = R.load_metric(name)
        assert reader.read(bare) is None
        if name != "host_gap_ms":
            assert reader.read(unscoped) is None


@pytest.fixture(scope="module")
def served():
    return spans.read(str(FIXTURE), json.loads(SCOPES.read_text()))


def test_fixture_readers_read_the_serving_trace(served):
    ctx = types.SimpleNamespace(spans=served)
    got = {name: R.load_metric(name).read(ctx) for name in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    steps = [s.end - s.start for s in served.serve("serve.step")]
    # the gap between steps is shorter than a step on the host
    assert got["host_gap_ms"] * 1e6 < max(steps)


def test_fixture_scope_split_closes(served):
    by, n, total = spans.split(served, "decode_step")
    assert n == len(spans.executions(served, "decode_step")) == 29
    assert set(by) <= spans.SCOPES | {spans.SCAN, spans.OTHER,
                                      spans.KERNELS}
    assert by[spans.SCAN] > 0 and by["attention"] > 0
    assert by[spans.KERNELS] > 0
    # the ops' self times cover the program's time but for the stalls
    # between its ops (2.6% of a 48 us two-layer step here; under 1% of
    # a full-size step)
    assert sum(by.values()) == pytest.approx(total, rel=0.03)
    assert sum(by.values()) < total


def test_fixture_kernels_named_and_scoped_by_leaf(served):
    assert set(spans.kernel_leaves(served, "decode_step")) == set(LEAVES)
    for e in spans.executions(served, "decode_step"):
        for op, _, kernel in e.ops:
            if kernel:
                leaf = spans.scope_of(served.scopes[e.program][op])
                assert op.startswith(f"tsmm_{leaf}")


def test_fixture_clock_puts_each_step_after_its_dispatch(served):
    ok, n = spans.clock_check(served)
    assert n == 29 and ok >= 0.95 * n


def test_fixture_host_gaps_and_idle_labels(served):
    gaps = spans.host_gaps(served)
    assert len(gaps) == 23 and min(gaps) > 0
    labels = spans.idle_labels(served)
    idle = sum(g1 - g0 for g0, g1 in served.gaps) * 1e-9
    assert sum(v for _, v, _ in labels) == pytest.approx(idle, rel=1e-9)
    assert sum(n for _, _, n in labels) == len(served.gaps)
    serve = sum(v for k, v, _ in labels if " / serve." in k
                or k.endswith(spans.OUTSIDE))
    assert serve == pytest.approx(idle, rel=1e-9)
    where = dict((k, v) for k, v, _ in spans.idle_by_program(served))
    assert sum(where.values()) == pytest.approx(idle, rel=1e-9)
    means = spans.span_means(served)
    assert means["serve.step"][0] == 29 and means["serve.admit"][0] == 6
