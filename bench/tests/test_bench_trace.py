"""The trace reduction, on synthetic intervals and on a small trace
recorded on a TPU v5e (``bench/tools/record_trace_fixture.py``): six
``decode_step`` calls (a Pallas kernel in a layer scan) and three
``prefill_row`` calls (one XLA matmul) inside the harness's window span,
with host sleeps between them, and one more call outside the window."""

from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).resolve().parent / "data" / "fixture.xplane.pb"


def test_union_merges_overlaps_and_nesting():
    total, merged = trace.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)])
    assert total == 30
    assert merged == [(0, 20), (30, 40)]


def test_self_time_subtracts_direct_children():
    # a while loop (0-100) around two body ops, one with a child
    events = [(0, 100), (10, 40), (15, 25), (50, 90)]
    assert trace.self_times(events) == [30, 20, 10, 40]


def test_clock_offset_finds_the_dispatch_shift():
    host = [1000.0 + 5e6 * i for i in range(8)]
    lag = [20e3, 35e3, 25e3, 50e3, 20e3, 30e3, 45e3, 21e3]
    dev = [h - 1.25e6 + l for h, l in zip(host, lag)]
    off = trace.clock_offset(dev, host)
    assert off == pytest.approx(-1.25e6 + 20e3, abs=1e3)


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(str(FIXTURE), ("jit__lambda",))


def test_fixture_programs_named_by_harness_span(reduced):
    kinds = reduced.by_kind()
    assert set(kinds) == {"decode_step", "prefill_row"}
    assert kinds["decode_step"]["n"] == 6
    assert kinds["prefill_row"]["n"] == 3


def test_fixture_kernel_time_only_in_the_program_with_the_kernel(reduced):
    kinds = reduced.by_kind()
    step, admit = kinds["decode_step"], kinds["prefill_row"]
    assert 0 < step["custom_ns"] < step["ns"]
    assert admit["custom_ns"] == 0
    # four layers: one kernel call each per step
    n_calls = sum(1 for m in reduced.op_self_ns if "tpu_custom_call" in m)
    assert n_calls >= 1


def test_fixture_busy_and_gaps_cover_the_window(reduced):
    w0, w1 = reduced.window
    idle = sum(g1 - g0 for g0, g1 in reduced.gaps)
    assert reduced.busy_ns + idle == pytest.approx(w1 - w0, rel=1e-9)
    # every op ran inside a program (programs also hold short stalls
    # between their ops), and the device was idle in the 2 ms host
    # sleeps between calls: mostly idle
    assert reduced.busy_ns <= sum(v["ns"] for v in reduced.by_kind().values())
    assert reduced.busy_ns < 0.05 * (w1 - w0)


def test_fixture_self_times_add_up_to_busy(reduced):
    own = sum(reduced.op_self_ns.values())
    assert own == pytest.approx(reduced.busy_ns, rel=0.02)


def test_fixture_idle_gaps_labelled_by_host_span(reduced):
    labels = dict(reduced.idle_by_host())
    sleeps = [v for k, v in labels.items() if k.startswith("no harness")]
    # the host slept 2 ms between calls, six times, with no span open
    assert sleeps and sleeps[0] > 6 * 2e-3
    assert any(k.startswith("decode_step") for k in labels)


def test_fixture_idle_gaps_labelled_by_client_state(reduced):
    w0, w1 = reduced.window
    mid = 0.5 * (w0 + w1)
    labels = dict(reduced.idle_by_host(
        lambda t: "early" if t < mid else "late"))
    assert any(", early (" in k for k in labels)
    assert any(", late" in k for k in labels)
    assert sum(labels.values()) == pytest.approx(
        sum(dict(reduced.idle_by_host()).values()), rel=1e-9)
