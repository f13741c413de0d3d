#!/usr/bin/env python3
"""Bring-up check: serve Qwen1.5-4B at its published width on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # tensor-parallel over four chips

One chip: the install-time sweep wall-clocks the Pallas candidates
(``repro.core.install --measure``) for the decode buckets and prefill
cells the serve phase uses; then ``serve.engine.Engine`` serves an
aligned group of 8 x 256-token prompts (``Engine.generate``: the flash
kernel and the m=2048 prefill TSMM) and a ragged queue of 8 requests
(``Engine.serve_queue``: continuous batching).  Four chips: the same
model sharded over a ``model=4`` mesh, compared with the one-device
forward, and no other phase.  Qwen1.5-4B: 40 layers, d_model 2560, 20
heads, d_ff 6912, vocab 151936; random weights from ``--seed``.

A run fails (exit 1) on any degradation-ladder demotion, a planned
kernel missing from a served program, a plan-registry miss after the
install sweep, served logits outside the bf16 band of the unplanned
forward, or a served kernel shape outside its band of an f32
``jnp.dot``.  With no TPU, or outside a checkout of the repository, it
exits 2 and prints no result.  The last line of a passing run is one
JSON object naming the device.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
STATE = ROOT / ".cache" / "chip_smoke"     # plan registry + measurements

ARCH = "qwen1_5_4b"
BUCKETS = (1, 2, 4, 8)
GROUP, PROMPT = 8, 256          # aligned group: flash + m=2048 prefill TSMM
RAGGED = 8                      # queue requests, prompt lengths 9..200
STEPS = 16                      # new tokens per request

# Bands, set from the dtype before any run.  bf16 keeps 8 significant
# bits (unit roundoff 2^-9 ~ 2e-3).  The served and the unplanned
# forward round activations to bf16 at different points (kernel
# accumulation order, fused epilogues) in each of 40 layers; 5e-2
# relative L2 allows ~25 roundoffs of drift.  A TSMM kernel rounds its f32
# accumulator to bf16 once: 1e-2 of the largest |value|.  Flash
# attention also rounds the probabilities to bf16 before the PV matmul.
LOGIT_BAND = 5e-2
KERNEL_BAND = 1e-2
ATTN_BAND = 2e-2
TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


class Run:
    """Phase timer with XLA compile accounting, and failure list."""

    def __init__(self):
        self.failures: list[str] = []
        self.xla = {"compile_s": 0.0, "hits": 0, "misses": 0}

    def watch_compiles(self) -> None:
        """Sum XLA compile seconds (a persistent-cache retrieval counts as
        one) and count persistent-cache hits and writes, from JAX's
        monitoring events."""
        from jax import monitoring

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.xla["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.xla["misses"] += 1

        def on_duration(event, duration_secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.xla["compile_s"] += duration_secs

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, before = time.perf_counter(), dict(self.xla)
        yield
        d = {k: v - before[k] for k, v in self.xla.items()}
        print(f"phase {name}: {time.perf_counter() - t0:.3f} s (XLA compile "
              f"{d['compile_s']:.3f} s; persistent cache {d['hits']} hits, "
              f"{d['misses']} misses)", flush=True)

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)
            print(f"CHECK FAILED: {what}", flush=True)


def _isolate_state() -> None:
    """Plans and measurements at a path this script owns, starting empty;
    no registry, find-db or program cache from outside the checkout."""
    for var in ("REPRO_TSMM_VARIANT", "REPRO_TSMM_SCHEDULE"):
        if os.environ.get(var):
            raise SystemExit(f"chip_smoke: {var} overrides the planned "
                             f"kernels; unset it")
    impl = os.environ.get("REPRO_TSMM_IMPL", "")
    if impl not in ("", "auto", "pallas"):
        raise SystemExit(f"chip_smoke: REPRO_TSMM_IMPL={impl!r} would not "
                         f"serve the Pallas path; unset it or use 'pallas'")
    STATE.mkdir(parents=True, exist_ok=True)
    paths = {"REPRO_PLAN_CACHE": STATE / "plans.json",
             "REPRO_MEASURE_CACHE": STATE / "measurements.json",
             "REPRO_MISS_LOG": STATE / "misses.json"}
    for var, path in paths.items():
        path.unlink(missing_ok=True)
        os.environ[var] = str(path)
    for var in ("REPRO_FIND_DB", "REPRO_TUNE_QUEUE", "REPRO_PROGRAM_CACHE"):
        os.environ.pop(var, None)


def _rel_l2(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _rel_max(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _reference_logits(model, params, prompts):
    """Last-position logits of the unplanned forward: outside the serving
    context every matmul is a plain ``jnp.dot`` (f32 accumulation inside,
    at the highest precision) and attention takes the jnp path."""
    import jax
    fn = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0][:, -1])
    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(fn(params, prompts))


def _served_prefill_logits(eng, prompts):
    """Last-position logits of the engine's own stored prefill program
    for the aligned group (a memory hit after ``generate``)."""
    import jax
    batch = eng.place_batch({"tokens": prompts})
    cache = eng.new_cache(GROUP)
    prog = eng.programs.program("prefill", (eng.params, batch, cache),
                                bucket=GROUP, tokens=PROMPT)
    logits, _ = prog.fn(eng.params, batch, cache)
    return jax.block_until_ready(logits[:, -1])


def _leaf_report(packable: dict, eng) -> None:
    """Packed leaves, and the packable ones left unpacked with their
    per-shard dims (a dim not a multiple of 128 has no kernel tiling)."""
    unpacked = {p: d for p, d in packable.items() if p not in eng.pack_report}
    print(f"leaves: packed {len(eng.pack_report)}, unpacked "
          f"{len(unpacked)} of {len(packable)} packable")
    for path, (rows, cols, rs, cs) in sorted(unpacked.items()):
        print(f"  unpacked {path}: per-shard {rows // rs} x {cols // cs}")


def _program_report(run: Run, eng, n_packed: int) -> dict:
    """``tpu_custom_call`` count per compiled program.  Every program
    calls each packed leaf's kernel once (the layer scan body appears
    once in the HLO); the aligned prefill also runs flash attention."""
    counts = {}
    for prog in eng.programs.handles():
        n = prog.executable.as_text().count(TPU_CUSTOM_CALL)
        short = prog.key.rsplit("_", 1)[0]
        counts[short] = n
        need = n_packed + (1 if prog.kind == "prefill" else 0)
        print(f"program {prog.key}: tpu_custom_call {n} (need >= {need})")
        run.check(n >= need, f"{short}: {n} tpu_custom_call < {need} "
                             f"planned kernels")
    for kind in ("prefill", "decode"):
        run.check(any(k.startswith(kind + "_") and n for k, n in
                      counts.items()),
                  f"no tpu_custom_call in any {kind} program")
    return counts


def _served_ms(eng) -> set:
    """Row counts m the served programs ran the packed kernels at."""
    out = set()
    for row in eng.programs.report():
        b, t = map(int, re.search(r"_b(\d+)_t(\d+)_", row["key"]).groups())
        out.add({"prefill": b * t, "decode": b, "prefill_row": t}[row["kind"]])
    return out


def _kernel_checks(run: Run, eng, ms: set) -> None:
    """Each served (m, packed weight) through ``tsmm_dot`` — the call the
    model makes — against an f32 ``jnp.dot`` of the same operands, and
    flash attention at the served shape against f32 softmax attention."""
    import jax
    import jax.numpy as jnp

    from repro.core.packing import PackedTensor, is_packed
    from repro.core.tsmm import tsmm_dot
    from repro.kernels.flash_attention import flash_attention
    from repro.resilience import degrade

    hi = jax.lax.Precision.HIGHEST
    weights = {}
    for leaf in jax.tree.leaves(eng.params, is_leaf=is_packed):
        if is_packed(leaf):
            blocks = leaf.blocks[0] if leaf.blocks.ndim == 5 else leaf.blocks
            weights.setdefault(
                (leaf.orig_rows, leaf.orig_cols),
                PackedTensor(blocks, leaf.orig_rows, leaf.orig_cols,
                             leaf.kernel_specs))
    def pair(key, w, m):
        a = jax.random.normal(key, (m, w.shape[0]), jnp.bfloat16)
        want = jnp.dot(a.astype(jnp.float32), w.unpack().astype(jnp.float32),
                       precision=hi)
        return tsmm_dot(a, w), want

    pair = jax.jit(pair, static_argnums=2)      # one program per shape
    stats = degrade.DegradeStats()
    key = jax.random.PRNGKey(1)
    worst = 0.0
    with degrade.use(stats):
        for m in sorted(ms):
            for (k, n), w in sorted(weights.items()):
                key, sub = jax.random.split(key)
                err = _rel_max(*pair(sub, w, m))
                worst = max(worst, err)
                run.check(err <= KERNEL_BAND,
                          f"kernel m={m} k={k} n={n}: error {err:.3e} > "
                          f"{KERNEL_BAND}")
    print(f"kernel checks: {len(ms) * len(weights)} (m, k, n) shapes, worst "
          f"error {worst:.3e} (band {KERNEL_BAND})")
    run.check(stats.total == 0, f"{stats.total} ladder demotions in the "
                                f"kernel checks")

    cfg = eng.model.cfg
    q, k, v = (jax.random.normal(s, (GROUP, cfg.num_heads, PROMPT,
                                     cfg.head_dim), jnp.bfloat16)
               for s in jax.random.split(key, 3))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=hi) * cfg.head_dim ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((PROMPT, PROMPT), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                      v.astype(jnp.float32), precision=hi)
    err = _rel_max(jax.jit(flash_attention)(q, k, v), want)
    print(f"flash attention check: error {err:.3e} (band {ATTN_BAND})")
    run.check(err <= ATTN_BAND, f"flash attention error {err:.3e} > "
                                f"{ATTN_BAND}")


def _ragged_requests(rng, vocab: int) -> list:
    from repro.serve.scheduler import Request
    lens = rng.integers(9, 201, size=RAGGED)
    lens[0], lens[-1] = 9, 200
    return [Request(tokens=rng.integers(0, vocab, size=int(n)).astype("int32"),
                    max_new_tokens=STEPS, rid=i) for i, n in enumerate(lens)]


def serve_one_chip(run: Run, args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import install, registry
    from repro.launch.serve import init_params, serving_max_len
    from repro.models.registry import build_model
    from repro.resilience import degrade
    from repro.serve.engine import Engine, iter_packable

    cfg = get_config(ARCH)
    with run.phase("install"):
        install.main(["--archs", ARCH, "--measure", "--max-batch",
                      str(BUCKETS[-1]), "--max-prompt", str(PROMPT),
                      "--iters", "3"])
    misses_installed = registry.stats()["misses"]

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (GROUP, PROMPT)),
                          jnp.int32)
    requests = _ragged_requests(rng, cfg.vocab_size)
    trace = [(GROUP, PROMPT)] + [(1, len(r.tokens)) for r in requests]
    max_len = serving_max_len(trace, STEPS, ragged=True)
    model = build_model(cfg)
    with run.phase("init"):
        params, axes = init_params(model, args.seed)
        jax.block_until_ready(params)
    with run.phase("reference"):
        ref = _reference_logits(model, params, prompts)
    packable = {"/".join(p): d for p, _, d in iter_packable(params, axes)}

    with run.phase("engine start"):
        eng = Engine(model, params, axes, max_len=max_len,
                     max_batch=BUCKETS[-1], max_prompt=PROMPT,
                     program_cache=False, donate_params=True)
        del params
    start_misses = registry.stats()["misses"] - misses_installed
    print(f"engine: max_len {max_len}, buckets {eng.buckets}, registry "
          f"misses at start {start_misses}")
    run.check(start_misses == 0, f"{start_misses} registry misses at engine "
                                 f"start")
    _leaf_report(packable, eng)

    with run.phase("serve aligned group"):
        res = eng.generate({"tokens": prompts}, steps=STEPS)
        jax.block_until_ready(res.tokens)
    print(f"aligned group: buckets {res.buckets}, prefill "
          f"{res.prefill_s:.3f} s, {res.per_token_s * 1e3:.2f} ms/token, "
          f"compile {res.compile_s:.3f} s")
    print(f"greedy tokens of request 0 (aligned): "
          f"{np.asarray(res.tokens[0]).tolist()}")
    err = _rel_l2(_served_prefill_logits(eng, prompts), ref)
    print(f"prefill logits vs unplanned forward: relative L2 error "
          f"{err:.3e} (band {LOGIT_BAND})")
    run.check(err <= LOGIT_BAND, f"logit error {err:.3e} > {LOGIT_BAND}")

    with run.phase("serve ragged queue"):
        results, stats = eng.serve_queue(requests)
    print(f"ragged queue: prompts {[len(r.tokens) for r in requests]}, "
          f"{stats.steps} decode steps")
    print(f"greedy tokens of request 0 (queue): {results[0].tokens.tolist()}")
    run.check(all(len(r.tokens) == STEPS for r in results),
              "a queued request did not produce all its tokens")

    with run.phase("checks"):
        _kernel_checks(run, eng, _served_ms(eng))
        _program_report(run, eng, len(eng.pack_report))
    misses = registry.stats()["misses"] - misses_installed
    print(f"registry misses after install: {misses}")
    run.check(misses == 0, f"{misses} registry misses after install")
    _report_engine(run, eng)
    run.check(degrade.GLOBAL.total == 0,
              f"{degrade.GLOBAL.total} ladder demotions outside the engine")


def serve_four_chips(run: Run, args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import registry
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import init_params, serving_max_len
    from repro.models.registry import build_model
    from repro.serve.engine import Engine, iter_packable
    from repro.sharding.rules import ShardingOptions

    if len(jax.devices()) < 4:
        raise SystemExit(f"chip_smoke --chips 4: JAX found "
                         f"{len(jax.devices())} device(s)")
    cfg = get_config(ARCH)
    mesh = make_mesh((4,), ("model",), devices=jax.devices()[:4])
    opts = ShardingOptions(dp_axes=())
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (GROUP, PROMPT)),
                          jnp.int32)
    model = build_model(cfg)
    with run.phase("init on one device"):
        params, axes = init_params(model, args.seed)
        jax.block_until_ready(params)
    with run.phase("reference on one device"):
        ref = _reference_logits(model, params, prompts)
    packable = {"/".join(p): d
                for p, _, d in iter_packable(params, axes, mesh, opts)}
    with run.phase("engine start (model=4)"):
        eng = Engine(model, params, axes,
                     max_len=serving_max_len([(GROUP, PROMPT)], STEPS,
                                             ragged=False),
                     max_batch=BUCKETS[-1], max_prompt=PROMPT, mesh=mesh,
                     opts=opts, program_cache=False, donate_params=True)
        del params
    print(f"engine: mesh {dict(mesh.shape)}, registry misses "
          f"{registry.stats()['misses']} (no install sweep in this path)")
    _leaf_report(packable, eng)
    with run.phase("serve aligned group (sharded)"):
        res = eng.generate({"tokens": prompts}, steps=STEPS)
        jax.block_until_ready(res.tokens)
    print(f"greedy tokens of request 0 (sharded): "
          f"{np.asarray(res.tokens[0]).tolist()}")
    err = _rel_l2(_served_prefill_logits(eng, prompts), ref)
    print(f"sharded prefill logits vs one-device unplanned forward: "
          f"relative L2 error {err:.3e} (band {LOGIT_BAND})")
    run.check(err <= LOGIT_BAND, f"logit error {err:.3e} > {LOGIT_BAND}")
    counts = {p.key.rsplit("_", 1)[0]:
              p.executable.as_text().count(TPU_CUSTOM_CALL)
              for p in eng.programs.handles()}
    print(f"tpu_custom_call per program: {counts}")
    run.check(all(counts.values()), "a sharded program runs no kernel")
    _report_engine(run, eng)


def _report_engine(run: Run, eng) -> None:
    import jax
    ps = eng.programs.stats()
    print(f"program store: {ps['programs']} programs, traced {ps['traced']}, "
          f"compile {ps['compile_s']:.3f} s (trace + lower "
          f"{ps['lower_s']:.3f} s, XLA compile "
          f"{ps['compile_s'] - ps['lower_s']:.3f} s)")
    hr = eng.health_report()
    print("health: " + json.dumps(hr, default=str))
    run.check(hr["healthy"], f"{hr['degradations']['total']} ladder "
                             f"demotions while serving")
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        peak, limit = ms.get("peak_bytes_in_use"), ms.get("bytes_limit")
        print(f"device {d.id}: peak_bytes_in_use {peak} of {limit}")
        run.check(peak is not None and limit is not None and peak < limit,
                  f"device {d.id}: peak {peak} not under {limit}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {Path(__file__).name}; run "
              f"it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    _isolate_state()
    from repro.serve.programs import enable_compilation_cache
    print(f"compilation cache: {enable_compilation_cache()}")
    print(f"device: {devices[0].device_kind} x {len(devices)}")

    run = Run()
    run.watch_compiles()
    with run.phase("total"):
        (serve_four_chips if args.chips == 4 else serve_one_chip)(run, args)
    if run.failures:
        print(f"chip_smoke: {len(run.failures)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
