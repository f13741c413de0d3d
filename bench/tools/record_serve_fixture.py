#!/usr/bin/env python3
"""Record the small serving trace that ``bench/tests/test_bench_spans.py``
reads.

    python3 bench/tools/record_serve_fixture.py [--out DIR]

Runs on a TPU.  A tiny dense model (two layers, the test drive's shapes)
is set up as a benchmark cell would be (plans, seeded weights, the
``Engine``, a warm-up pool), then served through ``AsyncEngine`` by the
harness's ``Server`` with its host spans on, under the profiler and the
harness's ``bench.window`` span: a few open-loop requests of mixed
prompt lengths, enough for admissions between decode steps and runs of
steps with none.  Writes under ``--out``:

- ``serve_fixture.xplane.pb``: the trace;
- ``serve_fixture_scopes.json``: for each stored program, the
  ``op_name`` of every instruction that the trace shows as an op.

Copy both to ``bench/tests/data/``.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

MODEL = dict(name="tiny", family="dense", num_layers=2, d_model=512,
             num_heads=4, num_kv_heads=2, head_dim=128, d_ff=1024,
             vocab_size=2048, qkv_bias=True, rope_theta=10000.0,
             norm_eps=1e-6, tie_embeddings=False, dtype="bfloat16")
ENGINE = dict(slots=4, max_len=160, max_prompt=64)
MIX = dict(loop="open", rate_rps=40.0,
           prompt_tokens=dict(median=24, sigma=0.6, min=8, max=64),
           output_tokens=dict(median=8, sigma=0.5, min=4, max=16),
           block=16, queue_limit=64, prefill_budget=32, sample=4)
# (prompt length, output length, due seconds after the window opens)
REQUESTS = [(12, 10, 0.0), (40, 6, 0.0), (20, 16, 0.004), (60, 8, 0.03),
            (9, 12, 0.06), (33, 16, 0.09)]


async def serve(b) -> None:
    import jax
    from bench import traffic
    from bench.serve_loop import Server, make_record, sleep_until

    server = Server(b.eng, MIX, b.slots, spans=True)
    await server.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        recs = []
        for i, (p, n, due) in enumerate(REQUESTS):
            await sleep_until(t0 + due)
            req = traffic.Req(index=i, due=due,
                              prompt=np.arange(p, dtype=np.int32) % 97 + 3,
                              out_len=n)
            rec = make_record(b.eng, req, t0 + due)
            recs.append(rec)
            server.offer(rec)
        for rec in recs:
            await rec.finished.wait()
    await server.stop()
    if not all(r.ok for r in recs):
        raise SystemExit("record_serve_fixture: a request did not complete")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=".cache/bench/serve_fixture")
    args = ap.parse_args(argv)
    out = Path(args.out).resolve()
    state = out / "state"
    state.mkdir(parents=True, exist_ok=True)
    for var, name in (("REPRO_PLAN_CACHE", "plans.json"),
                      ("REPRO_MEASURE_CACHE", "measurements.json"),
                      ("REPRO_MISS_LOG", "misses.json"),
                      ("REPRO_PROGRAM_CACHE", "programs")):
        os.environ[var] = str(state / name)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_serve_fixture: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from bench import run as R
    from bench import spans
    from bench import trace as T

    config = dict(model=MODEL, engine=ENGINE, reference="dense_lm",
                  check={"served_logit_gap": 0.3})
    cell = R.Cell(name="tiny.serve", chips=1, config=config, mix=MIX,
                  end_to_end=[], per_layer=[])
    b = R.Bench(cell, 3_000_000_019)
    b.setup()

    raw = out / "raw"
    shutil.rmtree(raw, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(raw), profiler_options=opts)
    asyncio.run(serve(b))
    jax.profiler.stop_trace()
    pb = glob.glob(str(raw / "**" / "*.xplane.pb"), recursive=True)[0]
    shutil.copy(pb, out / "serve_fixture.xplane.pb")

    seen = set()
    pd = jax.profiler.ProfileData.from_file(pb)
    for e in T._line(T._device_plane(pd), "XLA Ops"):
        seen.add(e.name.split(" = ", 1)[0].lstrip("%"))
    scopes = {}
    for prog in b.eng.programs.handles():
        name, ops = spans.scope_map(prog.executable.as_text())
        scopes[name] = {k: v for k, v in sorted(ops.items()) if k in seen}
    (out / "serve_fixture_scopes.json").write_text(
        json.dumps(scopes, indent=1, sort_keys=True) + "\n")
    st = spans.read(str(out / "serve_fixture.xplane.pb"), scopes)
    print(f"programs {sorted({e.program for e in st.execs})}; "
          f"host gaps {len(spans.host_gaps(st))}; clock check "
          f"{spans.clock_check(st)}; split {spans.split(st, 'decode_step')}; "
          f"size {(out / 'serve_fixture.xplane.pb').stat().st_size} B")
    return 0


if __name__ == "__main__":
    sys.exit(main())
