"""The traffic generator: deterministic per seed, clipped, and the same
work for every seed."""

import numpy as np
import pytest

from bench import traffic

MIXES = ("chat", "batch_gen")


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_per_seed(name):
    mix = traffic.load_mix(name)
    a = traffic.requests(mix, 64, 3_000_000_001, 1000)
    b = traffic.requests(mix, 64, 3_000_000_001, 1000)
    c = traffic.requests(mix, 64, 3_000_000_002, 1000)
    assert all(np.array_equal(x.prompt, y.prompt) and x.out_len == y.out_len
               and x.due == y.due for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_clips(name):
    mix = traffic.load_mix(name)
    reqs = traffic.requests(mix, 256, 2 ** 33 + 5, 151552)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.out_len <= o["max"] for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 151552 for r in reqs)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    mix = traffic.load_mix(name)
    n = 4 * int(mix["block"])
    runs = [traffic.requests(mix, n, s, 1000) for s in (1, 2 ** 31 + 7)]
    for field in (lambda r: len(r.prompt), lambda r: r.out_len):
        a, b = (sorted(map(field, reqs)) for reqs in runs)
        assert a == b
    if mix["loop"] == "open":
        # the gaps are one set of quantiles; each seed leaves out the one
        # that would follow its last request
        gaps = [np.round(np.diff([r.due for r in reqs]), 9) for reqs in runs]
        left = list(gaps[0])
        for g in gaps[1]:
            if g in left:
                left.remove(g)
        assert len(left) <= 1


def test_open_loop_dues_fill_the_window():
    mix = traffic.load_mix("chat")
    n = traffic.open_count(mix, 45)
    reqs = traffic.requests(mix, n, 7, 1000)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)
    assert dues[-1] >= 45
    due_in = sum(d < 45 for d in dues)
    assert abs(due_in - mix["rate_rps"] * 45) <= int(mix["block"])


def test_seed_changes_the_order_of_arrivals():
    mix = traffic.load_mix("chat")
    a, b = (traffic.requests(mix, 48, s, 1000) for s in (11, 2 ** 32 + 3))
    assert [r.due for r in a] != [r.due for r in b]
    assert [r.out_len for r in a] != [r.out_len for r in b]


def test_a_pool_is_fed_while_its_clock_has_room():
    """A request is fed while the pool's clock leaves room for its
    output after an eighth of the pool's decode room in the queue (128
    steps here); the first that does not fit is held, and so is every
    one behind it, until a new pool."""
    from types import SimpleNamespace

    from bench.serve_loop import Record, Server
    eng = SimpleNamespace(max_len=2048,
                          grid=SimpleNamespace(length=(128, 512, 1024)))
    server = Server(eng, dict(prefill_budget=32), 4)
    server.afe = SimpleNamespace(sched=SimpleNamespace(T=1024))
    submitted = []
    server._submit = submitted.append

    def rec(out_len):
        return Record(req=traffic.Req(index=0, due=0.0, prompt=None,
                                      out_len=out_len), due=0.0, lb=128)
    fits, short = rec(2048 - 1024 - 128), rec(8)
    server.hold.extend([fits, short])
    server._feed()
    assert submitted == [fits, short] and not server.draining
    server.afe.sched.T = 1025
    late, behind = rec(2048 - 1025 - 127), rec(8)
    server.hold.extend([late, behind])
    server._feed()
    assert submitted == [fits, short] and server.draining
    assert list(server.hold) == [late, behind]
