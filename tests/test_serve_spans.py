"""Host spans of the serving loop and names of the serving programs.

A tiny ``AsyncEngine`` run under the CPU profiler emits the ``serve.*``
spans nested as ``repro.serve.frontend`` and ``.scheduler`` document
them, with their arguments; the stored programs are lowered under stable
names, so a profile tells them apart by module name.
"""

import asyncio
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models.registry import build_model
from repro.serve.engine import Engine
from repro.serve.frontend import AsyncEngine
from repro.serve.programs import ProgramStore, program_name
from repro.serve.scheduler import Request


@pytest.fixture(scope="module")
def tiny():
    cfg = get_reduced_config("qwen1_5_4b").reduced(
        d_model=256, d_ff=512, num_layers=2, vocab_size=512,
        num_heads=4, num_kv_heads=4, head_dim=64, dtype="float32")
    model = build_model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    return model, params, axes


def _spans(path):
    """serve.* host spans as (name, start, end, args), by start."""
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def traced(tiny, tmp_path_factory):
    """Spans of four requests served by an AsyncEngine on the real clock,
    and the requests."""
    model, params, axes = tiny
    eng = Engine(model, params, axes, max_len=96, max_batch=2,
                 max_prompt=32, prepack=False)
    rng = np.random.default_rng(0)
    reqs = [Request(tokens=rng.integers(0, 512, n).astype(np.int32),
                    max_new_tokens=m, rid=100 + i)
            for i, (n, m) in enumerate([(5, 4), (17, 3), (9, 5), (30, 2)])]

    async def go():
        afe = AsyncEngine(eng, slots=2)
        loop = asyncio.ensure_future(afe.run())
        streams = [await afe.submit(r) for r in reqs]
        for s in streams:
            async for _ in s:
                pass
        afe.request_stop()
        await loop
        return streams

    asyncio.run(go())                 # compile every program first
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        streams = asyncio.run(go())
    assert all(s.completed for s in streams)
    pb = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)[0]
    return _spans(pb), reqs


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _within(spans, parent, name):
    return [s for s in spans if s[0] == name and _inside(s, parent)]


CHILDREN = ["serve.upload", "serve.dispatch", "serve.sample",
            "serve.readback", "serve.emit"]


def test_step_spans_nest_in_ticks_with_children_in_order(traced):
    spans, _ = traced
    ticks = [s for s in spans if s[0] == "serve.tick"]
    steps = [s for s in spans if s[0] == "serve.step"]
    assert steps and ticks
    for st in steps:
        assert sum(_inside(st, t) for t in ticks) == 1
        kids = [_within(spans, st, name) for name in CHILDREN]
        assert all(len(k) == 1 for k in kids), st
        starts = [k[0][1] for k in kids]
        assert starts == sorted(starts)
        assert st[3]["live"] >= 1
    # ``step`` counts the pool's earlier steps: 0, 1, 2, ... per pool
    counts = [s[3]["step"] for s in steps]
    assert counts == list(range(len(counts)))
    for name in ("serve.reap", "serve.deliver"):
        found = [s for s in spans if s[0] == name]
        assert found and all(any(_inside(s, t) for t in ticks)
                             for s in found)


def test_admit_spans_carry_the_request(traced):
    spans, reqs = traced
    admits = [s for s in spans if s[0] == "serve.admit"]
    assert sorted(a[3]["rid"] for a in admits) == [r.rid for r in reqs]
    by_rid = {r.rid: r for r in reqs}
    for a in admits:
        req = by_rid[a[3]["rid"]]
        assert a[3]["prompt"] == len(req.tokens)
        assert a[3]["lb"] >= len(req.tokens)
        kids = [_within(spans, a, name) for name in CHILDREN]
        assert all(len(k) == 1 for k in kids), a
        # an admission happens inside a tick, never inside a step
        assert not any(_inside(a, s) for s in spans if s[0] == "serve.step")
        assert any(_inside(a, t) for t in spans if t[0] == "serve.tick")


def test_program_names_tell_the_programs_apart():
    assert program_name("decode", 16, 1) == "decode_step_b16"
    assert program_name("prefill_row", 4, 512) == "prefill_row_b4_t512"
    assert program_name("prefill", 2, 64) == "prefill_b2_t64"
    names = {program_name(k, b, t) for k in ("decode", "prefill_row")
             for b in (1, 4) for t in (1, 128, 256)}
    assert len(names) == 2 + 2 * 3


def test_stored_programs_are_lowered_under_their_names(tiny, tmp_path):
    model, params, axes = tiny
    store = ProgramStore(model, cache_dir=False)
    cache = model.init_cache(2, 32)
    tok = jax.numpy.zeros((2, 1), jax.numpy.int32)
    prog = store.program("decode", (params, cache, tok), bucket=2, tokens=1)
    head = prog.executable.as_text().split(",", 1)[0]
    assert head == "HloModule jit_decode_step_b2"
    # the key keeps its structural form (kind, bucket, length bucket)
    assert prog.key.startswith("decode_b2_t1_")
    batch = {"tokens": jax.numpy.zeros((1, 16), jax.numpy.int32),
             "pad": jax.numpy.zeros((1,), jax.numpy.int32)}
    row = jax.numpy.asarray(0, jax.numpy.int32)
    prog = store.program("prefill_row", (params, batch, cache, row, row),
                         bucket=2, tokens=16)
    assert prog.executable.as_text().startswith(
        "HloModule jit_prefill_row_b2_t16,")
