"""Serving engine: batch-adaptive pre-packed decode (DESIGN.md §7).

The load path is where the paper's install-time/pre-pack pipeline runs for
real: every linear weight the decode step will hit is planned by the
autotuner and re-laid-out into block-major ``PackedTensor``s ONCE, with
block shapes conforming to EVERY power-of-two batch bucket; thereafter
every decoded token replays the bucket's execution plan (the paper's
data-reuse scenario, where pack cost amortizes to zero).

Request admission: an incoming request group of any size b <= max_batch is
padded up to the nearest bucket and served from that bucket's stored
program — variable decode traffic never re-packs weights and never
recompiles once a bucket is warm.  Groups larger than max_batch are split.

Since §13 the compiled programs live in a :class:`~repro.serve.programs.
ProgramStore` instead of ad-hoc ``jax.jit`` wrappers: every (bucket,
shape) program is AOT-lowered once and persisted, so an engine restarted
against a populated cache (``install --precompile``) performs zero traces
on first traffic.  Passing a CONCRETE ``mesh`` turns on tensor-parallel
sharded serving as a first-class mode: params, cache, batch and token
placement all follow ``sharding/rules.py`` and the stored programs carry
explicit in/out shardings.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh

from repro.core.plan import BucketGrid, Problem, bucket_for, buckets_for, \
    length_buckets_for
from repro.core.tsmm import prepack_for
from repro.resilience import degrade
from repro.serve.clock import StepCost, ensure_clock
from repro.serve.programs import ProgramStore
from repro.models.param import is_axes_leaf
from repro.sharding.context import sharding_ctx
from repro.sharding.rules import (ShardingOptions, axis_size, packed_pspec,
                                  pspec_for)

log = logging.getLogger(__name__)

# Leaves consumed through core.linear (packable).  MoE expert tensors are
# consumed by batched einsum and excluded (see DESIGN.md §4), as is MLA's
# ``wkv_b``, which the absorbed decode reads as per-head einsum operands.
PACKABLE = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_in",
            "w_out", "head", "wq_a", "wq_b", "wkv_a", "ws_gate", "ws_up",
            "ws_down"}
MIN_ROWS, MIN_COLS = 512, 512


def packable_divisors(path, axes_leaf, leaf, mesh=None,
                      opts: Optional[ShardingOptions] = None):
    """The single source of truth for "is this leaf packed, and how is it
    sharded": returns (rows, cols, row_shards, col_shards) or None.

    Shared by the serving pre-pack (real arrays) and the install sweep's
    shape enumeration (ShapeDtypeStructs), so the Problem keys both sides
    produce match by construction."""
    name = path[-1]
    if name not in PACKABLE or leaf.ndim < 2 or leaf.ndim > 3:
        return None
    if leaf.ndim == 3 and axes_leaf[0] not in ("layers", "groups"):
        return None
    rows, cols = leaf.shape[-2:]
    if rows < MIN_ROWS or cols < MIN_COLS:
        return None
    rs = cs = 1
    if mesh is not None:
        spec = pspec_for(axes_leaf, leaf.shape, mesh, opts or ShardingOptions())
        rs = axis_size(mesh, spec[-2]) if spec[-2] else 1
        cs = axis_size(mesh, spec[-1]) if spec[-1] else 1
    return rows, cols, rs, cs


def iter_packable(params, axes, mesh=None,
                  opts: Optional[ShardingOptions] = None):
    """Yield (path, leaf, (rows, cols, rs, cs)) for every packable leaf.
    ``params`` may hold arrays or ShapeDtypeStructs."""
    def walk(p, a, path):
        if isinstance(p, dict):
            for k in p:
                yield from walk(p[k], a[k], path + (k,))
            return
        d = packable_divisors(path, a, p, mesh, opts)
        if d is not None:
            yield path, p, d

    yield from walk(params, axes, ())


def pack_tree_for_serving(params, axes, batch_m, mesh=None,
                          opts: Optional[ShardingOptions] = None, *,
                          donate: bool = False):
    """Replace packable weight leaves with planned PackedTensors.

    ``batch_m``: the serving batch size, or a tuple of batch buckets — with
    buckets the chosen blocks conform to every bucket (DESIGN.md §7) so one
    packed tree serves all of them.  ``donate`` deletes each unpacked leaf
    as soon as its packed copy exists, so the device holds one tree plus
    one leaf at the peak instead of both trees (the caller's ``params``
    must not be used afterwards).
    Returns (packed_params, report: {path: blocks_shape}).
    """
    opts = opts or ShardingOptions()
    report = {}

    def walk(p, a, path):
        if isinstance(p, dict):
            return {k: walk(p[k], a[k], path + (k,)) for k in p}
        d = packable_divisors(path, a, p, mesh, opts)
        if d is None:
            return p
        _, _, rs, cs = d
        # num_shards keys the tuned Problem: a sharded engine must look up
        # the same registry entries the (sharded) install sweep wrote
        pk = prepack_for(batch_m, p, num_shards=rs * cs,
                         shard_divisors=(rs, cs))
        if pk is None:
            return p
        report["/".join(path)] = tuple(pk.blocks.shape)
        if mesh is not None:
            spec = packed_pspec(a, pk, mesh, opts)
            pk.shard_axes = (spec[-4], spec[-3])
        if donate:
            p.delete()
        return pk

    from repro.core import registry
    misses_before = registry.stats()["misses"]
    packed = walk(params, axes, ())
    if registry.stats()["misses"] > misses_before:
        registry.flush()   # persist freshly tuned plans in ONE write;
    # after an install sweep every lookup hits and no write happens
    return packed, report


class _BackgroundTuner:
    """Measures registry-missed problems off-thread and commits winners
    (DESIGN.md §9 runtime miss path).

    On a registry miss the engine serves IMMEDIATELY off the
    calibrated-model plan the autotuner produced at trace time; the
    missed problem keys are drained here, wall-clocked on a daemon
    thread with the adaptive short-list search, and the measured winner
    is committed back to the registry — admission never blocks on a
    stopwatch.  The registry's provenance guard makes the commit safe
    against concurrent model-ranked puts from the serving thread.

    With a fleet tuning ``queue`` attached (DESIGN.md §15) the tuner
    defers to the fleet: any missed key the queue already owns —
    pending, leased by a worker, or measured (done) — is skipped here,
    so a miss is measured exactly once fleet-wide even when a host runs
    its own background tuner alongside the worker fleet."""

    def __init__(self, hw=None, *, top_k: int = 4, stable: int = 2,
                 iters: int = 3, warmup: int = 1, queue=None):
        self.hw = hw
        self.queue = queue
        self.top_k, self.stable = top_k, stable
        self.iters, self.warmup = iters, warmup
        self.committed: list = []
        self._seen: set = set()
        self._threads: list = []
        self._lock = threading.Lock()

    def submit(self, problem_keys: list) -> None:
        with self._lock:
            fresh = [k for k in problem_keys if k not in self._seen]
            self._seen.update(fresh)
        if not fresh:
            return
        t = threading.Thread(target=self._work, args=(fresh,), daemon=True,
                             name="repro-bg-tuner")
        with self._lock:
            self._threads.append(t)
        t.start()

    def busy(self) -> bool:
        with self._lock:
            return any(t.is_alive() for t in self._threads)

    def join(self, timeout: Optional[float] = None) -> None:
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(timeout)

    def _work(self, keys: list) -> None:
        from repro.core import registry
        from repro.core.autotuner import make_plan
        if self.queue is not None:
            try:
                fleet_owned = self.queue.active_keys()
            except Exception:
                log.exception("fleet queue unreadable; tuning locally")
                fleet_owned = set()
            deferred = [k for k in keys if k in fleet_owned]
            keys = [k for k in keys if k not in fleet_owned]
            if deferred:
                log.info("background tuner: %d misses deferred to the "
                         "fleet queue", len(deferred))
        for key in keys:
            try:
                cur = registry.peek(key)
                if cur is not None and cur.chosen_by == "measured":
                    continue             # a previous run already timed it
                plan = make_plan(Problem.from_key(key), self.hw,
                                 measure="wallclock", force=True,
                                 persist=False, top_k=self.top_k,
                                 stable=self.stable, iters=self.iters,
                                 warmup=self.warmup)
                self.committed.append(plan)
                log.info("background tuner committed %s", plan)
            except Exception:
                log.exception("background tune failed for %s", key)
        registry.flush()                 # plans + measurement records


@dataclasses.dataclass
class GenerateResult:
    tokens: jnp.ndarray          # (B, steps)
    logits_last: jnp.ndarray
    prefill_s: float = 0.0
    per_token_s: float = 0.0
    buckets: tuple = ()          # bucket(s) the group was served from
    # first-invocation (trace + jit compile + first run) time of this
    # group's prefill/decode programs — included in prefill_s/per_token_s
    # but reported separately so throughput comparisons can use warm time
    compile_s: float = 0.0


class Engine:
    """Batch-adaptive greedy-decoding engine with aligned positions.

    Requests are padded to a common prompt length and decoded in lockstep
    (continuous batching with aligned steps — the regime the decode_32k
    cell models: 128 streams x one token each against a 32k cache).

    The engine owns power-of-two batch buckets 1..max_batch.  Weights are
    packed ONCE with blocks conforming to all buckets; each bucket gets
    its own compiled prefill/decode programs (jit shape specialization),
    all closing over the same packed param tree.  A legacy fixed-batch
    caller (``batch_size=N``) gets the full bucket set; pass
    ``buckets=(N,)`` to pin single-bucket planning/packing.

    ``donate_params=True`` hands the engine the caller's weights: each
    unpacked leaf is deleted once its packed copy exists, which is what
    lets a model whose weights fill most of the device be packed at all.
    """

    def __init__(self, model, params, axes, *, max_len: int,
                 batch_size: Optional[int] = None,
                 max_batch: Optional[int] = None,
                 buckets: Optional[tuple] = None,
                 max_prompt: Optional[int] = None, min_prompt: int = 8,
                 mesh=None, opts: Optional[ShardingOptions] = None,
                 prepack: bool = True, background_tune: bool = False,
                 tuner_opts: Optional[dict] = None, tune_queue=None,
                 program_cache=None,
                 clock=None, step_cost: Optional[StepCost] = None,
                 donate_params: bool = False):
        if max_batch is None:
            max_batch = batch_size
        self.model = model
        self.mesh = mesh
        self.opts = opts or ShardingOptions()
        # sharded serving is a first-class mode, gated on a CONCRETE mesh
        # (an AbstractMesh still shapes packing divisors / lowering, but
        # there is nothing to place arrays on)
        self.sharded = (isinstance(mesh, Mesh)
                        and getattr(mesh, "devices", None) is not None)
        # clock seam (DESIGN.md §12): every serving-path time read goes
        # through here; a VirtualClock makes telemetry deterministic (the
        # engine/scheduler charge step_cost instead of measuring)
        self.clock = ensure_clock(clock)
        self.step_cost = step_cost or StepCost()
        # §16 resilience plane: every ladder demotion on this engine's
        # serving paths (kernel fallback, disk-program retrace, deferred
        # registry flush, ...) is counted here; health_report() reads it
        self.degrade = degrade.DegradeStats()
        self.tuner: Optional[_BackgroundTuner] = None
        # fleet mode (DESIGN.md §15): with a tune_queue attached (or
        # REPRO_TUNE_QUEUE set) the fleet's workers own measurement.
        # background_tune=False is the documented fleet default — misses
        # then flush to the persisted miss log for harvest instead of
        # being tuned in-process (see _drain_misses).
        if tune_queue is None and os.environ.get("REPRO_TUNE_QUEUE", ""):
            from repro.tuning.queue import JobQueue
            tune_queue = JobQueue()
        self.tune_queue = tune_queue
        if background_tune:
            # close the measure -> model -> plan loop: trace-time misses
            # rank against the measurement-calibrated model, and missed
            # problems get wall-clocked + committed off-thread below
            from repro.core import autotuner, evaluator
            hw = evaluator.calibrated_hw(autotuner.default_hw())
            autotuner.set_default_hw(hw)
            self.tuner = _BackgroundTuner(hw, queue=tune_queue,
                                          **(tuner_opts or {}))
        if buckets:
            self.buckets = tuple(sorted(buckets))
            # the largest admissible chunk is the largest bucket: bigger
            # groups are split, never crashed; with no explicit ceiling
            # the bucket set IS the ceiling
            self.max_batch = (min(max_batch, self.buckets[-1])
                              if max_batch is not None else self.buckets[-1])
        else:
            if max_batch is None:
                raise TypeError("Engine needs one of batch_size, max_batch "
                                "or buckets")
            self.max_batch = max_batch
            self.buckets = buckets_for(self.max_batch)
        self.batch_size = self.max_batch     # legacy alias
        self.max_len = max_len
        # 2D admission grid (DESIGN.md §8): ragged prompts pad to a length
        # bucket; plans / jit programs are keyed (batch-bucket, len-bucket)
        self.grid = BucketGrid(
            self.buckets,
            length_buckets_for(min(max_prompt or max_len, max_len),
                               min_prompt))
        if prepack:
            with degrade.use(self.degrade):
                params, report = pack_tree_for_serving(
                    params, axes, self.buckets, mesh, self.opts,
                    donate=donate_params)
            log.info("pre-packed %d weight leaves for buckets %s",
                     len(report), self.buckets)
            self.pack_report = report
        else:
            self.pack_report = {}
        self.axes = axes
        param_sh = None
        if self.sharded:
            from repro.sharding.rules import param_shardings
            param_sh = param_shardings(axes, params, mesh, self.opts)
            params = jax.device_put(params, param_sh)
        self.params = params
        # the program store replaces the old per-bucket jax.jit wrappers:
        # every (kind, bucket, shape) program is AOT-lowered once, kept
        # warm in memory and persisted on disk, so an engine restarted
        # against an `install --precompile`d cache traces NOTHING
        self.programs = ProgramStore(model, mesh=mesh, opts=self.opts,
                                     param_shardings=param_sh,
                                     cache_dir=program_cache)
        self._drain_misses()

    # -- placement (sharded mode) ---------------------------------------

    def new_cache(self, batch_size: int):
        """A fresh decode cache, placed on the mesh in sharded mode."""
        return self.place_cache(self.model.init_cache(batch_size,
                                                      self.max_len))

    def place_cache(self, cache):
        if not self.sharded:
            return cache
        return self.programs.place(cache, self.programs.cache_shardings(cache))

    def place_batch(self, batch):
        if not self.sharded:
            return batch
        return self.programs.place(batch, self.programs.batch_shardings(batch))

    def place_tokens(self, tok):
        if not self.sharded:
            return tok
        return self.programs.place(tok, self.programs.tokens_sharding(tok))

    def place_scalar(self, x):
        if not self.sharded:
            return x
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    def _stamp_report(self, field: int) -> dict:
        """Walk every PackedTensor's ``kernel_specs`` stamp and map
        ``m{bucket}_k{k}_n{n}`` -> the stamped entry's ``field`` (1 =
        KernelSpec, 2 = ScheduleSpec).  Entries stamped before the
        schedule axis existed are (bucket, spec) pairs — their schedule
        reads as the default."""
        from repro.core.packing import PackedTensor
        from repro.core.plan import DEFAULT_SCHEDULE
        out = {}
        leaves = jax.tree.leaves(
            self.params, is_leaf=lambda x: isinstance(x, PackedTensor))
        for leaf in leaves:
            if not isinstance(leaf, PackedTensor):
                continue
            k, n = leaf.shape[-2:]
            for entry in leaf.kernel_specs:
                val = entry[field] if len(entry) > field else DEFAULT_SCHEDULE
                out[f"m{entry[0]}_k{k}_n{n}"] = val.key()
        return out

    def variant_report(self) -> dict:
        """Which kernel variant each packed weight will replay per batch
        bucket — read off the ``kernel_specs`` stamp ``prepack_for`` left
        on every PackedTensor (DESIGN.md §10), so the report is exact for
        sharded engines too (whose registry keys use per-shard dims).
        Keys are ``m{bucket}_k{k}_n{n}`` strings, values
        ``KernelSpec.key()``; unstamped/uncovered buckets are absent
        (they serve the baseline)."""
        return self._stamp_report(1)

    def schedule_report(self) -> dict:
        """Schedule-axis sibling of :func:`variant_report` (DESIGN.md
        §11): which grid schedule each packed weight replays per bucket
        (``ScheduleSpec.key()`` values; ``default`` = the pre-schedule
        behavior)."""
        return self._stamp_report(2)

    # -- background tuning (runtime miss path, DESIGN.md §9) ------------

    def _drain_misses(self) -> None:
        """Hand any registry misses since the last drain to the
        background tuner — serving already ran off the model-ranked
        plans; measurement must never block the serving thread.

        Without a tuner (``background_tune=False``, the documented fleet
        mode, DESIGN.md §15) the misses flush to the persisted miss log
        instead: the fleet's ``harvest`` step turns them into queue jobs
        and the workers do the measuring.  A no-op when nothing missed,
        so warm lookup-only serving never touches the file."""
        from repro.core import registry
        with degrade.use(self.degrade):
            if self.tuner is None:
                registry.flush_misses()
                return
            keys = registry.drain_misses()
        if keys:
            log.info("background-tuning %d registry misses", len(keys))
            self.tuner.submit(keys)

    # -- bucket dispatch ------------------------------------------------

    def bucket_of(self, b: int) -> int:
        return bucket_for(b, self.buckets)

    @staticmethod
    def _pad_group(batch: dict, b: int, bucket: int) -> dict:
        if b == bucket:
            return batch
        def pad(x):
            if not hasattr(x, "ndim") or x.ndim == 0 or x.shape[0] != b:
                return x
            return jnp.pad(x, ((0, bucket - b),) + ((0, 0),) * (x.ndim - 1))
        return {k: pad(v) for k, v in batch.items()}

    # -- generation -----------------------------------------------------

    def generate(self, batch: dict, steps: int) -> GenerateResult:
        """Serve one request group of ANY size: groups <= max_batch are
        padded to the nearest bucket; larger groups are split into
        max_batch chunks and merged."""
        b = batch["tokens"].shape[0]
        if b <= self.max_batch:
            return self._generate_bucket(batch, steps)
        parts = []
        for lo in range(0, b, self.max_batch):
            hi = min(lo + self.max_batch, b)
            chunk = {k: (v[lo:hi] if hasattr(v, "ndim") and v.ndim
                         and v.shape[0] == b else v)
                     for k, v in batch.items()}
            parts.append(self._generate_bucket(chunk, steps))
        return GenerateResult(
            tokens=jnp.concatenate([r.tokens for r in parts], axis=0),
            logits_last=jnp.concatenate([r.logits_last for r in parts], axis=0),
            prefill_s=sum(r.prefill_s for r in parts),
            per_token_s=sum(r.per_token_s for r in parts),
            buckets=tuple(bk for r in parts for bk in r.buckets),
            compile_s=sum(r.compile_s for r in parts),
        )

    def _generate_bucket(self, batch: dict, steps: int) -> GenerateResult:
        clock = self.clock
        b = batch["tokens"].shape[0]
        bucket = self.bucket_of(b)
        batch = self._pad_group(batch, b, bucket)
        width = batch["tokens"].shape[-1]
        compile_s = 0.0
        from repro.core.linear import serving_ctx
        with serving_ctx(), sharding_ctx(self.mesh, self.opts), \
                degrade.use(self.degrade):
            cache = self.new_cache(bucket)
            batch = self.place_batch(batch)
            # a cold (bucket, prompt-shape) program acquire is AOT
            # lower+compile — or a disk-cache deserialize — inside the
            # timed window, so compile_s keeps the same meaning the lazy
            # jit wrappers gave it and throughput stays warm-honest
            t0 = clock.now()
            pprog = self.programs.program(
                "prefill", (self.params, batch, cache),
                bucket=bucket, tokens=width)
            logits, cache = jax.block_until_ready(
                pprog.fn(self.params, batch, cache))
            if clock.virtual:
                if pprog.cold:
                    clock.advance(self.step_cost.compile_s)
                clock.advance(self.step_cost.prefill_s(bucket * width))
            t1 = clock.now()
            if pprog.cold:
                compile_s += t1 - t0
            toks = []
            tok = self.place_tokens(
                jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32))
            dprog = None
            for i in range(steps):
                toks.append(tok)
                if i == 0:
                    td = clock.now()
                    dprog = self.programs.program(
                        "decode", (self.params, cache, tok),
                        bucket=bucket, tokens=1)
                    logits, cache = dprog.fn(self.params, cache, tok)
                    if dprog.cold:
                        jax.block_until_ready(logits)
                        if clock.virtual:
                            clock.advance(self.step_cost.compile_s)
                        compile_s += clock.now() - td
                else:
                    logits, cache = dprog.fn(self.params, cache, tok)
                if clock.virtual:
                    clock.advance(self.step_cost.decode_step_s)
                tok = self.place_tokens(
                    jnp.argmax(logits[:, -1], axis=-1)[:, None]
                    .astype(jnp.int32))
            jax.block_until_ready(tok)
            t2 = clock.now()
        self._drain_misses()
        return GenerateResult(
            tokens=jnp.concatenate(toks, axis=1)[:b],
            logits_last=logits[:b],
            prefill_s=t1 - t0,
            per_token_s=(t2 - t1) / max(steps, 1),
            buckets=(bucket,),
            compile_s=compile_s,
        )

    def ragged_supported(self) -> bool:
        cfg = self.model.cfg
        return (self.model.prefill_row is not None
                and not cfg.embeds_input
                and not getattr(cfg, "is_encoder_decoder", False))

    def serve(self, requests: list, steps: int) -> list:
        """Admission layer over ``generate``: a list of single requests
        (dicts with 1D ``tokens``) becomes one aligned group.

        Ragged prompt lengths are admitted by left-padding every prompt to
        the group's length bucket with per-row attention masking
        (``batch["pad"]``, DESIGN.md §8) — positions stay aligned, so
        decode remains lockstep.  Returns one GenerateResult per request
        (views into the group result)."""
        if not requests:
            return []
        lens = sorted({int(r["tokens"].shape[-1]) for r in requests})
        keys = requests[0].keys()
        if not self.ragged_supported():
            if len(lens) != 1:
                raise ValueError(
                    f"ragged prompt lengths {lens} need an attention-cache "
                    f"LM (family={self.model.cfg.family}); pad the prompts "
                    f"to a common length for this architecture")
            lb = lens[-1]
        elif lens[-1] > self.grid.max_prompt:
            lb = lens[-1]      # beyond the grid: serve at the raw max
        else:
            # uniform groups bucket too: one prefill program and one set
            # of planned token counts per length bucket, not per raw
            # length (the warm-program / lookup-only contract)
            lb = self.grid.length_bucket(lens[-1])
        if len(lens) == 1 and lens[0] == lb:
            group = {k: jnp.stack([jnp.asarray(r[k]) for r in requests])
                     for k in keys}
        else:
            toks, pads = [], []
            for r in requests:
                t = jnp.asarray(r["tokens"])
                pad = lb - t.shape[-1]
                toks.append(jnp.pad(t, (pad, 0)))
                pads.append(pad)
            group = {"tokens": jnp.stack(toks),
                     "pad": jnp.asarray(pads, jnp.int32)}
            for k in keys:
                if k not in ("tokens", "pad"):
                    group[k] = jnp.stack([jnp.asarray(r[k])
                                          for r in requests])
        res = self.generate(group, steps)
        return [GenerateResult(tokens=res.tokens[i:i + 1],
                               logits_last=res.logits_last[i:i + 1],
                               prefill_s=res.prefill_s,
                               per_token_s=res.per_token_s,
                               buckets=res.buckets,
                               compile_s=res.compile_s)
                for i in range(len(requests))]

    def serve_queue(self, requests: list, *, slots: Optional[int] = None):
        """Continuous batching (DESIGN.md §8): serve a queue of
        :class:`repro.serve.scheduler.Request`s with *different* prompt
        lengths and per-request stop state from a fixed slot pool —
        finished streams free their slot mid-flight and queued requests
        join the running decode batch.  Returns (results, stats)."""
        from repro.serve.scheduler import ContinuousScheduler
        out = ContinuousScheduler(self, slots=slots).run(requests)
        self._drain_misses()
        return out

    # -- resilience telemetry (DESIGN.md §16) ---------------------------

    def health_report(self) -> dict:
        """One dict answering "is this engine serving at full fidelity?":
        every degradation-ladder demotion since construction (zero on a
        healthy run — the ``serve --health`` CI contract), the circuit
        breaker's open keys, any armed failpoints, and the program-store
        counters.  Shape is stable for automation; ``launch/serve.py
        --health`` pretty-prints it and exits non-zero on degradations."""
        from repro.resilience import failpoints
        rep = self.degrade.report()
        return {
            "healthy": rep["total"] == 0,
            "degradations": rep,
            "failpoints": failpoints.report(),
            "programs": self.programs.stats(),
        }
