"""Install-time stage CLI — the paper's 'assembly kernel selector' run
once per machine/platform.

    PYTHONPATH=src python -m repro.core.install [--measure] [--calibrate]
                                                [--archs a,b] [--iters N]
                                                [--shapes N]
                                                [--max-batch N]
                                                [--max-prompt S]
                                                [--mesh data=4,model=2]
                                                [--check]

Pre-populates the persistent plan registry with execution plans for every
TSMM-shaped matmul the model zoo's serving path will hit, over the 2D
bucket grid (DESIGN.md §8):

* decode: every power-of-two batch bucket (1..max_batch) x each arch's
  projection shapes;
* prefill: every (batch-bucket x length-bucket) cell's token count
  (``bb * lb``) x the same shapes.

A subsequent Engine start is then registry lookups only — the runtime
stage never tunes.  With ``--measure`` the performance evaluator times the
model-ranked short-list (adaptive early stop; wall-clock; on TPU this
times the Pallas kernels), recording every timing into the persistent
measurement cache so repeated sweeps reuse old records.  With
``--calibrate`` the roofline coefficients are least-squares fitted from
that cache (DESIGN.md §9) and the whole sweep is RE-RANKED under the
calibrated model — measured winners are preserved by the registry's
provenance guard, while every un-measured shape inherits the fit.  With
``--check`` the sweep runs against a fresh in-memory registry and FAILS if
any lookup misses — the CI contract that a warm cache file fully covers
the serving path.
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.configs import ARCH_IDS, get_config
from repro.core import registry
from repro.core.autotuner import make_plan_grid, make_plan_set
from repro.core.plan import (BucketGrid, Problem, buckets_for, is_tsmm,
                             length_buckets_for)
from repro.core.registry import cache_path

# Serving batch buckets swept at install time (replaces the old fixed
# DECODE_BATCHES tuple): every power of two up to the fleet's max batch.
MAX_SERVE_BATCH = 128
SERVE_BUCKETS = buckets_for(MAX_SERVE_BATCH)
# Prompt-length buckets swept for the prefill path (ragged admission).
MAX_SERVE_PROMPT = 512
SERVE_LENGTHS = length_buckets_for(MAX_SERVE_PROMPT)


def serving_shapes(cfg) -> set:
    """The (k, n) weight shapes that ``core.linear`` sees on the serving
    path of one arch: attention's projections (latent attention's with
    ``use_mla``), the dense MLP (in a MoE model only its ``first_k_dense``
    layers), the shared experts, the SSM projections and the head.  The
    routed experts and the router are einsums, not ``linear`` calls."""
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    shapes = set()
    if cfg.use_mla:
        dr, dv, qr, kvr = (cfg.rope_head_dim, cfg.v_head_dim,
                           cfg.q_lora_rank, cfg.kv_lora_rank)
        shapes |= ({(d, qr), (qr, h * (hd + dr))} if qr
                   else {(d, h * (hd + dr))})
        shapes |= {(d, kvr + dr), (kvr, h * (hd + dv)), (h * dv, d)}
    elif h:
        shapes |= {(d, h * hd), (d, kh * hd), (h * hd, d)}
    if cfg.d_ff and (not cfg.num_experts or cfg.first_k_dense):
        shapes |= {(d, cfg.d_ff), (cfg.d_ff, d)}
    if cfg.num_shared_experts:
        sff = cfg.num_shared_experts * cfg.d_ff_expert
        shapes |= {(d, sff), (sff, d)}
    if cfg.ssm_state:
        di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
        shapes |= {(d, 2 * di + 2 * g * n + cfg.ssm_heads), (di, d)}
    shapes.add((d, cfg.vocab_size))
    return shapes


def sharded_serving_shapes(cfg, mesh, opts=None) -> set:
    """Per-shard (k_shard, n_shard, num_shards) for every packable weight
    leaf of the arch under ``mesh`` — the exact Problem keys a sharded
    engine's pre-pack looks up (same walk: ``serve.engine.iter_packable``
    over ``jax.eval_shape`` structs, no parameter allocation)."""
    import jax

    from repro.models.registry import build_model
    from repro.serve.engine import iter_packable

    model = build_model(cfg)
    captured = {}

    def init_shapes(rng):
        params, axes = model.init(rng)
        captured["axes"] = axes     # pure python, safe to keep from tracing
        return params

    shapes = jax.eval_shape(init_shapes, jax.random.PRNGKey(0))
    out = set()
    for _path, _leaf, (rows, cols, rs, cs) in iter_packable(
            shapes, captured["axes"], mesh, opts):
        if rows % rs or cols % cs:
            continue                # prepack_for refuses these outright
        out.add((rows // rs, cols // cs, rs * cs))
    return out


def parse_mesh(spec: str):
    """``data=4,model=2`` -> an AbstractMesh with those axis sizes.

    Sharding divisors only need axis NAMES and SIZES (``pspec_for`` /
    ``axis_size``), so the install host needs no actual devices — the
    sweep can run on a workstation for any target pod slice."""
    from jax.sharding import AbstractMesh
    axes = []
    for part in spec.split(","):
        name, size = part.split("=")
        axes.append((name.strip(), int(size)))
    return AbstractMesh(tuple(axes))


def concrete_mesh(spec: str):
    """``data=4,model=2`` -> a real device Mesh, or None when the host
    has too few devices.  ``--precompile`` needs one: XLA compiles (and
    serializes) sharded executables only against concrete devices."""
    import math

    import jax

    from repro.launch.mesh import make_mesh
    axes = [(name.strip(), int(size))
            for name, size in (p.split("=") for p in spec.split(","))]
    need = math.prod(s for _, s in axes)
    devs = jax.devices()
    if len(devs) < need:
        return None
    return make_mesh(tuple(s for _, s in axes),
                     tuple(name for name, _ in axes), devices=devs[:need])


def serving_problems(cfg, buckets: tuple = SERVE_BUCKETS,
                     lengths: tuple = ()) -> list[Problem]:
    """The (m, k, n) set the serving path hits for one architecture:
    every batch bucket (decode, m = bb) plus — when ``lengths`` is given —
    every grid cell's token count (prefill, m = bb * lb)."""
    shapes = sorted(serving_shapes(cfg))
    ms = list(buckets)
    if lengths:
        grid = BucketGrid(tuple(buckets), tuple(lengths))
        ms = sorted(set(ms) | set(grid.token_buckets()))
    out = []
    for m in ms:
        for (k, n) in shapes:
            if is_tsmm(m, k, n):
                out.append(Problem(m, k, n, cfg.dtype))
    return out


def install_arch(cfg, buckets: tuple = SERVE_BUCKETS,
                 lengths: tuple = (), *, mesh=None, opts=None,
                 measure: bool = False, hw=None, iters: int = 5,
                 limit_shapes: int = 0, force: bool = False) -> int:
    """Sweep one arch's serving shapes over the bucket grid.  Plans land
    in the in-memory registry; the caller flushes once (bulk write).

    With ``mesh`` the per-shard shapes of every packable leaf are swept
    too (num_shards-keyed), so a sharded Engine start is also lookup-only.
    ``hw``/``force`` drive the calibrated re-rank pass (re-tune every
    problem under a fitted HwSpec; the registry keeps measured winners);
    ``limit_shapes`` caps the (k, n) shapes per arch for tiny CI sweeps.
    """
    n_plans = 0
    mm = "wallclock" if measure else None
    shard_shapes = set()
    if mesh is not None:
        shard_shapes = {s for s in sharded_serving_shapes(cfg, mesh, opts)
                        if s[2] > 1}
    shapes = sorted(serving_shapes(cfg))
    if limit_shapes:
        shapes = shapes[:limit_shapes]
    for (k, n) in shapes:
        pset = make_plan_set(k, n, buckets, cfg.dtype, hw=hw, measure=mm,
                             persist=False, iters=iters, force=force)
        n_plans += len(pset.plans)
        if lengths:
            grid = BucketGrid(tuple(buckets), tuple(lengths))
            pg = make_plan_grid(k, n, grid, cfg.dtype, hw=hw, measure=mm,
                                persist=False, iters=iters, force=force)
            # cells sharing a token count share a plan; count distinct
            n_plans += len({p.problem.m for p in pg.plans.values()
                            if p.problem.m not in buckets})
    for (ks, ns, s) in sorted(shard_shapes):
        pset = make_plan_set(ks, ns, buckets, cfg.dtype, num_shards=s, hw=hw,
                             measure=mm, persist=False, iters=iters,
                             force=force)
        n_plans += len(pset.plans)
    return n_plans


def precompile_arch(cfg, buckets: tuple, lengths: tuple, *, max_len: int,
                    mesh=None, opts=None, cache_dir=None) -> list:
    """AOT-compile one arch's serving program grid into the persistent
    program cache (the ``--precompile`` phase; DESIGN.md §13).  Returns
    the per-program report rows from ``serve.programs.precompile_grid``;
    a later Engine start with the same shape envelope traces nothing."""
    import jax

    from repro.models.registry import build_model
    from repro.serve.programs import precompile_grid

    model = build_model(cfg)
    captured = {}

    def init_shapes(rng):
        params, axes = model.init(rng)
        captured["axes"] = axes
        return params

    jax.eval_shape(init_shapes, jax.random.PRNGKey(0))
    return precompile_grid(model, captured["axes"], buckets=buckets,
                           lengths=lengths, max_len=max_len, mesh=mesh,
                           opts=opts, cache_dir=cache_dir)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--measure", action="store_true",
                    help="wall-clock the short-list (evaluator stage; "
                         "records land in the persistent measurement "
                         "cache and are reused across runs)")
    ap.add_argument("--calibrate", action="store_true",
                    help="least-squares fit the roofline coefficients "
                         "from the measurement cache and re-rank the "
                         "whole sweep under the calibrated model "
                         "(measured winners are preserved)")
    ap.add_argument("--iters", type=int, default=5,
                    help="timed iterations per measured candidate")
    ap.add_argument("--shapes", type=int, default=0,
                    help="cap (k, n) serving shapes per arch "
                         "(0 = all; for tiny CI measure sweeps)")
    ap.add_argument("--archs", default="")
    ap.add_argument("--max-batch", type=int, default=MAX_SERVE_BATCH,
                    help="largest serving batch; buckets are powers of two "
                         "up to this")
    ap.add_argument("--max-prompt", type=int, default=MAX_SERVE_PROMPT,
                    help="largest prompt-length bucket for the prefill "
                         "sweep (0 disables the length axis)")
    ap.add_argument("--mesh", default="",
                    help="target mesh axis sizes, e.g. data=4,model=2 — "
                         "also sweeps every packable leaf's per-shard "
                         "shapes so a SHARDED engine start is lookup-only "
                         "(no devices needed on the install host)")
    ap.add_argument("--check", action="store_true",
                    help="verify-only: re-run the sweep against the cache "
                         "file with a fresh memory and fail on any registry "
                         "miss (the engine-start-is-lookup-only contract)")
    ap.add_argument("--precompile", action="store_true",
                    help="also AOT-compile the serving program grid into "
                         "the persistent program cache (REPRO_PROGRAM_CACHE)"
                         " — a same-shaped Engine start then traces NOTHING")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (CI-sized) configs — pairs with "
                         "--precompile for tractable compile sweeps")
    ap.add_argument("--max-len", type=int, default=0,
                    help="engine cache capacity precompiled programs "
                         "assume (0 = 2 x max-prompt); must match "
                         "Engine(max_len=...) for the cache to hit")
    ap.add_argument("--program-cache", default="",
                    help="program-cache directory override for --precompile")
    ap.add_argument("--find-db", default="",
                    help="attach a fleet find-db artifact (DESIGN.md §15) "
                         "before the sweep: sets REPRO_FIND_DB so "
                         "--check validates serving coverage against the "
                         "exported artifact, not just the local cache")
    args = ap.parse_args(argv)
    from repro.serve.programs import enable_compilation_cache
    enable_compilation_cache()
    if args.find_db:
        from repro.tuning.find_db import attach
        attach(args.find_db)
    archs = ([a.strip() for a in args.archs.split(",") if a.strip()]
             or ARCH_IDS)
    buckets = buckets_for(args.max_batch)
    lengths = length_buckets_for(args.max_prompt) if args.max_prompt else ()
    mesh = parse_mesh(args.mesh) if args.mesh else None

    def cfg_of(arch):
        if args.reduced:
            from repro.configs import get_reduced_config
            return get_reduced_config(arch)
        return get_config(arch)

    if args.check:
        registry.clear_memory()

    t0 = time.time()
    n_plans = 0
    for arch in archs:
        cfg = cfg_of(arch)
        n = install_arch(cfg, buckets, lengths, mesh=mesh,
                         measure=args.measure, iters=args.iters,
                         limit_shapes=args.shapes)
        if not args.check:
            registry.flush()   # one write per arch: an interrupted sweep
        n_plans += n           # (a killed --measure run) keeps its work
        print(f"{arch:24s} {n:3d} plans")

    if args.check:
        stats = registry.stats()
        if stats["misses"]:
            print(f"CHECK FAILED: {stats['misses']} registry misses — the "
                  f"cache at {cache_path()} does not cover the serving "
                  f"sweep (hits={stats['hits']})")
            sys.exit(1)
        print(f"check ok: {stats['hits']} lookups, all hits "
              f"-> {cache_path()}")
        # kernel-grammar self-check (DESIGN.md §10/§14): run a sampled
        # sweep of the synthesis grammar — every legacy-equivalent point
        # plus strided novel points — in interpret mode on one tiny
        # shape: an unemittable or numerically broken grammar point must
        # fail the workflow before a tuned registry can ever point
        # serving at it.
        from repro.kernels.variants import verify_variants
        rows = verify_variants(impl="pallas_interpret")
        bad = [r for r in rows if not r["ok"]]
        for r in rows:
            status = "ok" if r["ok"] else f"FAILED ({r['error']})"
            print(f"variant {r['spec']:20s} {r['orientation']:9s} {status}")
        if bad:
            print(f"CHECK FAILED: {len(bad)}/{len(rows)} kernel variants "
                  f"broken")
            sys.exit(1)
        print(f"variant check ok: {len(rows)} sampled grammar points "
              f"verified in interpret mode")
        # grid-schedule self-check (DESIGN.md §11): every enumerable
        # schedule x every variant it applies to, in interpret mode —
        # the same gate the variant axis gets, so a broken M-partition
        # grid or semantics override can never reach a tuned registry.
        from repro.kernels.variants import verify_schedules
        rows = verify_schedules(impl="pallas_interpret")
        bad = [r for r in rows if not r["ok"]]
        for r in bad:
            print(f"schedule {r['schedule']:24s} {r['spec']:20s} "
                  f"{r['orientation']:9s} FAILED ({r['error']})")
        if bad:
            print(f"CHECK FAILED: {len(bad)}/{len(rows)} grid schedules "
                  f"broken")
            sys.exit(1)
        print(f"schedule check ok: {len(rows)} (variant x schedule) "
              f"combinations verified in interpret mode")
        return

    if args.calibrate:
        from repro.core.autotuner import default_hw
        from repro.core.evaluator import MIN_FIT_RECORDS, calibrated_hw
        hw_cal = calibrated_hw(default_hw())
        n_rec = len(registry.measurements())
        if not hw_cal.calibrated:
            if n_rec < MIN_FIT_RECORDS:
                print(f"calibrate: only {n_rec} cached measurements "
                      f"(need >= {MIN_FIT_RECORDS}) — skipped; run with "
                      f"--measure first")
            else:
                print(f"calibrate: fit over {n_rec} measurements is "
                      f"degenerate (collinear roofline features) — "
                      f"skipped; measure a more shape-diverse sweep")
        else:
            print(f"calibrated from {n_rec} measurements: "
                  f"eff_hbm={hw_cal.hbm_bw * hw_cal.hbm_efficiency/1e9:.2f}GB/s "
                  f"(x{hw_cal.hbm_efficiency:.3g}) "
                  f"mxu_eff=x{hw_cal.mxu_efficiency:.3g} "
                  f"grid_overhead={hw_cal.grid_overhead_s:.3g}s")
            for arch in archs:
                install_arch(cfg_of(arch), buckets, lengths, mesh=mesh,
                             measure=False, hw=hw_cal, force=True,
                             limit_shapes=args.shapes)
            registry.flush()
            print("re-ranked sweep under the calibrated model "
                  "(measured winners preserved)")

    if args.precompile:
        from repro.serve.programs import program_cache_dir
        from repro.sharding.rules import ShardingOptions
        pmesh, popts = None, None
        if args.mesh:
            pmesh = concrete_mesh(args.mesh)
            if pmesh is None:
                import jax
                print(f"precompile: mesh '{args.mesh}' needs real devices "
                      f"(host has {len(jax.devices())}) — compiling "
                      f"unsharded instead")
            else:
                popts = ShardingOptions(dp_axes=tuple(
                    a for a in ("pod", "data") if a in pmesh.shape))
        max_len = args.max_len or 2 * (lengths[-1] if lengths else 64)
        tp = time.time()
        for arch in archs:
            rows = precompile_arch(cfg_of(arch), buckets, lengths,
                                   max_len=max_len, mesh=pmesh, opts=popts,
                                   cache_dir=args.program_cache or None)
            traced = sum(1 for r in rows if r["source"] == "traced")
            print(f"{arch:24s} {len(rows):3d} programs "
                  f"({traced} compiled, {len(rows) - traced} cached) "
                  f"compile_s={sum(r['compile_s'] for r in rows):.1f}")
        print(f"precompiled serving grids in {time.time()-tp:.1f}s "
              f"-> {args.program_cache or program_cache_dir()}")

    print(f"\ninstalled {n_plans} execution plans over buckets {buckets} "
          f"x lengths {lengths or '(none)'} in {time.time()-t0:.1f}s "
          f"-> {cache_path()}")


if __name__ == "__main__":
    main()
