"""Serving launcher CLI: batch-adaptive pre-packed decode.

Fixed-size group (legacy):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1_5_4b --reduced \
        --batch 4 --prompt-len 32 --steps 16

Mixed-batch trace (bucketed runtime, DESIGN.md §7) — each comma-separated
entry is one request group admitted against the bucket set:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1_5_4b --reduced \
        --trace 3,17,64 --max-batch 64 --steps 8

Ragged trace (continuous batching, DESIGN.md §8) — ``b:p`` entries are
``b`` requests with prompt length ``p``; mixed lengths (or ``--queue``)
route the whole trace through the slot-pool scheduler, which prints its
telemetry (padding waste, queue latency, slot occupancy):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1_5_4b --reduced \
        --trace 2:9,3:30,1:5 --max-batch 4 --steps 8

Open-loop async front end (DESIGN.md §12) — the same trace becomes a
seeded Poisson arrival process at ``--rate`` requests/s, served through
the SLO-aware ``AsyncEngine`` (priority tiers, tenant fairness,
bounded-queue backpressure, chunk-budgeted prefill) on the
deterministic virtual clock; prints the p50/p95/p99 TTFT scoreboard and
per-tier telemetry:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1_5_4b --reduced \
        --trace 2:9,3:30,1:5 --max-batch 4 --steps 8 --async --rate 50
"""

from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, get_reduced_config
from repro.models.registry import build_model
from repro.serve.engine import Engine
from repro.serve.programs import enable_compilation_cache
from repro.serve.scheduler import Request


def init_params(model, seed: int = 0):
    """Random weights from ``seed`` as ``(params, axes)``.  Built in ONE
    jitted program: eager init would materialize every layer before
    stacking them, twice the weights in device memory at the peak.  The
    key uses the hardware bit generator: the threefry program for a
    model's worth of weights takes minutes to compile."""
    captured = {}

    def init(rng):
        params, axes = model.init(rng)
        captured["axes"] = axes     # pure python, safe to keep from tracing
        return params

    params = jax.jit(init)(jax.random.key(seed, impl="unsafe_rbg"))
    return params, captured["axes"]


def serving_max_len(trace: list, steps: int, *, ragged: bool) -> int:
    """Cache capacity for a trace of ``(group size, prompt length)``
    entries.  Ragged serving runs the continuous-batching pool on one
    global clock: the base length bucket plus every decode step of every
    request.  An aligned group needs its prompt plus its steps."""
    max_prompt = max(p for _, p in trace)
    if ragged:
        return 2 * max_prompt + sum(b * steps for b, _ in trace) + 8
    return max_prompt + steps + 8


def make_group(cfg, b: int, prompt_len: int) -> dict:
    batch = {"tokens": (jnp.arange(b * prompt_len)
                        .reshape(b, prompt_len)
                        % cfg.vocab_size).astype(jnp.int32)}
    if cfg.embeds_input:
        batch["embeds"] = jnp.zeros(
            (b, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.is_encoder_decoder:
        batch["enc_frames"] = jnp.zeros(
            (b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    return batch


def parse_trace(spec: str, default_len: int) -> list:
    """Each entry: ``b`` (group of b at the default prompt length) or
    ``b:p`` (group of b requests with prompt length p)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            b, p = part.split(":")
            out.append((int(b), int(p)))
        else:
            out.append((int(part), default_len))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--override", default="",
                    help="comma-separated config overrides (k=v ints, e.g. "
                         "d_model=512,num_layers=1) applied on top of the "
                         "selected config — the fleet-tuning CI uses this "
                         "to shape a reduced config into TSMM territory")
    ap.add_argument("--find-db", default="",
                    help="attach a fleet find-db artifact (DESIGN.md §15): "
                         "sets REPRO_FIND_DB so the registry overlays the "
                         "exported plans at load")
    ap.add_argument("--require-warm", action="store_true",
                    help="exit 1 if serving logged ANY registry miss or "
                         "traced ANY program — the fleet 'restart is "
                         "lookup-only' CI gate")
    ap.add_argument("--health", action="store_true",
                    help="print the engine's resilience health report "
                         "(DESIGN.md §16 degradation ladder) after serving "
                         "and exit 1 if ANY ladder demotion fired — the "
                         "'happy path serves undegraded' CI gate")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--trace", default="",
                    help="comma-separated request groups: sizes (3,17,64) "
                         "or b:prompt_len pairs (2:9,3:30) — mixed lengths "
                         "run the continuous-batching scheduler")
    ap.add_argument("--queue", action="store_true",
                    help="force the continuous-batching scheduler even for "
                         "a uniform-length trace")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="bucket ceiling (default: largest group)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0)
    ap.add_argument("--no-prepack", action="store_true")
    ap.add_argument("--mesh", default="",
                    help="serve tensor-parallel sharded: axis sizes over "
                         "this host's devices, e.g. model=2 (DESIGN.md "
                         "§13); fails if the host has too few devices")
    ap.add_argument("--program-cache", default="",
                    help="program-cache dir override ('off' disables "
                         "persistence; default REPRO_PROGRAM_CACHE, else "
                         "under the compilation cache's directory)")
    ap.add_argument("--background-tune", action="store_true",
                    help="on registry miss, serve off the calibrated-model "
                         "plan and wall-clock + commit the measured winner "
                         "on a background thread (DESIGN.md §9)")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="open-loop SLO-aware front end (DESIGN.md §12): "
                         "requests arrive as a Poisson process at --rate "
                         "on the deterministic virtual clock")
    ap.add_argument("--rate", type=float, default=25.0,
                    help="offered load for --async, requests/s")
    ap.add_argument("--queue-limit", type=int, default=64,
                    help="--async admission-control bound (backpressure)")
    ap.add_argument("--prefill-budget", type=int, default=32,
                    help="--async prompt tokens admissible per decode step "
                         "(0 = unbounded)")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    enable_compilation_cache()
    if args.find_db:
        from repro.tuning.find_db import attach
        attach(args.find_db)
    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    if args.override:
        overrides = {}
        for part in args.override.split(","):
            k, _, v = part.strip().partition("=")
            if k:
                overrides[k] = int(v)
        cfg = cfg.reduced(**overrides)
    model = build_model(cfg)

    trace = parse_trace(args.trace, args.prompt_len) or [(args.batch,
                                                          args.prompt_len)]
    max_batch = args.max_batch or max(b for b, _ in trace)
    max_prompt = max(p for _, p in trace)
    ragged = args.queue or len({p for _, p in trace}) > 1
    max_len = args.max_len or serving_max_len(
        trace, args.steps, ragged=args.async_mode or ragged)

    mesh = opts = None
    if args.mesh:
        from repro.core.install import concrete_mesh
        from repro.sharding.rules import ShardingOptions
        mesh = concrete_mesh(args.mesh)
        if mesh is None:
            raise SystemExit(f"--mesh {args.mesh}: host has only "
                             f"{len(jax.devices())} devices")
        opts = ShardingOptions(dp_axes=tuple(
            a for a in ("pod", "data") if a in mesh.shape))
    program_cache = (False if args.program_cache.lower() in ("off", "0", "none")
                     else args.program_cache) if args.program_cache else None
    # the engine takes the only reference to the weights: each unpacked
    # leaf is freed as soon as its packed copy exists
    eng = Engine(model, *init_params(model), max_len=max_len,
                 max_batch=max_batch, max_prompt=max_prompt,
                 prepack=not args.no_prepack,
                 background_tune=args.background_tune, mesh=mesh, opts=opts,
                 program_cache=program_cache, donate_params=True)
    print(f"buckets={eng.buckets} length_buckets={eng.grid.length} "
          f"packed_leaves={len(eng.pack_report)}"
          + (f" mesh={dict(mesh.shape)}" if mesh is not None else ""))

    def epilogue():
        from collections import Counter

        from repro.core import registry
        s = registry.stats()
        print(f"plan registry: {s['hits']} hits / {s['misses']} misses")
        ps = eng.programs.stats()
        print(f"program store: {ps['programs']} programs "
              f"(traced={ps['traced']} disk={ps['from_disk']} "
              f"reused={ps['reused']}) compile={ps['compile_s']:.2f}s "
              f"load={ps['load_s']:.2f}s cache={ps['cache_dir']}")
        vr = eng.variant_report()
        if vr:
            counts = Counter(vr.values())
            print("kernel variants in play: "
                  + ", ".join(f"{k} x{v}"
                              for k, v in sorted(counts.items())))
        sr = eng.schedule_report()
        if sr:
            counts = Counter(sr.values())
            print("grid schedules in play: "
                  + ", ".join(f"{k} x{v}"
                              for k, v in sorted(counts.items())))
        if eng.tuner is not None:
            eng.tuner.join(timeout=300)
            print(f"background tuner committed {len(eng.tuner.committed)} "
                  f"measured plans "
                  f"({len(registry.measurements())} cached measurements)")
        if args.require_warm and (s["misses"] or ps["traced"]):
            raise SystemExit(
                f"--require-warm: serving was NOT lookup-only "
                f"({s['misses']} registry misses, {ps['traced']} traced "
                f"programs) — stale find-db or program cache?")
        if args.health:
            import json as _json
            hr = eng.health_report()
            print("-- health report (DESIGN.md §16) --")
            print(_json.dumps(hr, indent=2, default=str))
            if not hr["healthy"]:
                raise SystemExit(
                    f"--health: {hr['degradations']['total']} degradation(s) "
                    f"fired — serving ran off the ladder, not the plan")

    if args.async_mode:
        from repro.serve.clock import VirtualClock
        from repro.serve.frontend import AsyncEngine

        rng = np.random.default_rng(0)
        reqs = []
        arrival = 0.0
        for i, (b, p) in enumerate(trace):
            for j in range(b):
                arrival += float(rng.exponential(1.0 / args.rate))
                reqs.append(Request(
                    tokens=rng.integers(0, cfg.vocab_size, size=p),
                    max_new_tokens=args.steps, rid=f"g{i}r{j}",
                    arrival_time=arrival, priority=i % 3,
                    tenant=f"tenant{j % 2}"))
        afe = AsyncEngine(eng, queue_limit=args.queue_limit,
                          prefill_budget=args.prefill_budget or None,
                          clock=VirtualClock())
        streams, stats = afe.simulate(reqs)
        for s in streams:
            state = ("REJECTED" if s.rejected
                     else "ok" if s.completed else "truncated")
            ttft = f"{s.ttft * 1e3:7.2f}ms" if s.ttft is not None else "      -"
            print(f"req {str(s.rid):8s} tier={s.priority} "
                  f"tenant={s.tenant:8s} arrive={s.arrival_time:7.3f}s "
                  f"ttft={ttft} tokens={len(s.tokens):3d} {state}")
        ttfts = np.asarray([s.ttft for s in streams if s.ttft is not None])
        if ttfts.size:
            print(f"-- offered load {args.rate:g} req/s (virtual clock) --")
            print(f"  ttft p50/p95/p99: {np.percentile(ttfts, 50)*1e3:.2f} / "
                  f"{np.percentile(ttfts, 95)*1e3:.2f} / "
                  f"{np.percentile(ttfts, 99)*1e3:.2f} ms")
        print("-- scheduler telemetry --")
        for k, v in stats.rows():
            print(f"  {k:20s} {v}")
        epilogue()
        return

    if ragged:
        rng = np.random.default_rng(0)
        reqs = [Request(tokens=rng.integers(0, cfg.vocab_size, size=p),
                        max_new_tokens=args.steps, rid=f"g{i}r{j}")
                for i, (b, p) in enumerate(trace) for j in range(b)]
        results, stats = eng.serve_queue(reqs)
        for r in results:
            print(f"req {str(r.rid):8s} prompt={r.prompt_len:4d} "
                  f"lb={r.length_bucket:4d} admitted@{r.admitted_at} "
                  f"done@{r.finished_at} waited={r.queue_steps} "
                  f"tokens={list(map(int, r.tokens[:8]))}"
                  f"{'...' if len(r.tokens) > 8 else ''}")
        print("-- scheduler telemetry --")
        for k, v in stats.rows():
            print(f"  {k:20s} {v}")
        epilogue()
        return

    for b, p in trace:
        res = eng.generate(make_group(cfg, b, p), steps=args.steps)
        print(f"group b={b:4d} -> buckets={res.buckets} "
              f"prefill={res.prefill_s:.3f}s "
              f"per_token={res.per_token_s*1e3:.2f}ms")
        print("  tokens[0]:", list(map(int, res.tokens[0])))
    epilogue()


if __name__ == "__main__":
    main()
