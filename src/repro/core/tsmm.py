"""Public TSMM API: planned matmul + distributed variants.

``tsmm_dot`` is the single entry point applications use; it consults the
plan registry (runtime stage) and dispatches to the pre-packed Pallas path
for tall-and-skinny shapes, falling back to plain XLA GEMM otherwise —
mirroring how MKL dispatches TSMM vs GEMM.

The distributed forms encode the paper's multi-thread optimizer at mesh
scale:

* :func:`distributed_tsmm` shards the TALL dim over the mesh axis and
  replicates the skinny operand — each device computes its full output
  rows with NO collectives (the GEBB_t "no synchronization" property).
* :func:`conventional_ksplit` is the conventional-library baseline: split
  the contraction dim, all-reduce partials.  Implemented so the benchmark
  suite can reproduce the paper's conventional-GEMM comparison.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import registry
from repro.resilience import degrade, failpoints
from repro.core.autotuner import (default_hw, make_plan, make_plan_set,
                                  plan_for_matmul)
from repro.core.hw import TPU_V5E, HwSpec
from repro.core.packing import PackedTensor, is_packed, pack
from repro.core.plan import (Plan, Problem, ScheduleSpec, is_tsmm,
                             parse_schedule)
from repro.core.vmem_model import feasible, predict
from repro.kernels import ops, variants
from repro.kernels.variants import KernelSpec

log = logging.getLogger(__name__)


def _gemm_epilogue(a2, w, bias, act, out_dtype):
    """The unplanned fallback: plain XLA GEMM accumulating in f32 (like
    every planned path) with a post-hoc epilogue — the bottom rung of
    the §16 kernel ladder, always lowerable."""
    out = jnp.dot(a2, w, preferred_element_type=jnp.float32).astype(out_dtype)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    if act is not None:
        from repro.kernels.ref import act_ref
        out = act_ref(out.astype(jnp.float32), act).astype(out.dtype)
    return out


def _laddered(orientation: str, breaker_key: str, planned, xla_twin, gemm):
    """Run one planned TSMM down the §16 degradation ladder.

    Planning happens at trace time, so a variant whose Pallas lowering
    fails raises HERE — catchable — and the call demotes: planned
    variant -> the same blocked structure as an XLA twin -> unplanned
    GEMM + epilogue.  Numerics are preserved at every rung (all three
    accumulate in f32); only speed degrades — each demotion is counted
    on the ambient :class:`~repro.resilience.degrade.DegradeStats`.  The
    circuit breaker stops re-attempting a deterministically-failing
    variant key after K failures and pins its fallback."""
    stats = degrade.current()
    breaker = stats.breaker
    if breaker.allow(breaker_key):
        try:
            failpoints.fp(f"kernels.lower.{orientation}")
            out = planned()
            breaker.success(breaker_key)
            return out
        except Exception as e:  # noqa: BLE001 — lowering/compile failure
            opened = breaker.failure(breaker_key)
            log.warning("tsmm: planned %s variant failed for %s (%s); "
                        "degrading to XLA twin%s", orientation, breaker_key,
                        e, " [breaker OPEN: fallback pinned]" if opened
                        else "")
            stats.record("kernel.variant", key=breaker_key, fallback="xla",
                         error=str(e))
    else:
        # breaker open: the planned variant is known-bad — serve the
        # pinned fallback without paying the failed attempt again
        stats.record("kernel.pinned", key=breaker_key, fallback="xla")
    try:
        failpoints.fp(f"kernels.xla.{orientation}")
        return xla_twin()
    except Exception as e:  # noqa: BLE001
        log.warning("tsmm: blocked-XLA twin failed for %s (%s); degrading "
                    "to unplanned GEMM", breaker_key, e)
        stats.record("kernel.xla", key=breaker_key, fallback="gemm",
                     error=str(e))
        return gemm()


def _mosaic_mesh(impl: Optional[str]):
    """The ambient serving mesh when ``impl`` lowers to Mosaic kernels,
    else None.  XLA cannot partition a Mosaic kernel, so under a mesh each
    kernel runs per shard inside ``shard_map``."""
    from repro.sharding.context import get_ctx
    ctx = get_ctx()
    if ctx is None or ops._resolve(impl) == "xla":
        return None
    return ctx.mesh


def _skinny_per_shard(mesh, b: PackedTensor, run, a2, bias, act):
    """``run(x, blocks, bias, act, layer)`` on each device's shard of the
    packed weight ``b`` (its ``shard_axes``; a layer view's stacked
    blocks keep their layer dim whole).  Column blocks sharded: each
    device computes its own output columns, bias and activation fused.
    Row blocks sharded: each device contracts its k panel and the f32
    partials are summed across the axis before bias and activation.
    Returns (m, padded n)."""
    row_ax, col_ax = b.shard_axes
    nk, nn, bk, bn = b.blocks.shape[-4:]
    a2 = ops.pad2(a2, a2.shape[0], nk * bk)
    view = b.layer is not None
    args = [a2, b.blocks] + [b.layer] * view
    specs = ([P(None, row_ax), P(*(None,) * view, row_ax, col_ax, None, None)]
             + [P()] * view)
    if bias is not None and row_ax is None:
        args.append(ops._pad_bias(bias, nn * bn))
        specs.append(P(col_ax))

    def local(x, w, *rest):
        layer, bias_ = (rest[0] if view else None), rest[view:]
        if row_ax is None:
            return run(x, w, bias_[0] if bias_ else None, act, layer)
        part = run(x, w, None, None, layer).astype(jnp.float32)
        return jax.lax.psum(part, row_ax)

    out = jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                        out_specs=P(None, col_ax), check_vma=False)(*args)
    if row_ax is None:
        return out
    if bias is not None:
        out = out + ops._pad_bias(bias, nn * bn).astype(jnp.float32)
    from repro.kernels.ref import act_ref
    return act_ref(out, act).astype(a2.dtype)


def impl_choice() -> str:
    """``REPRO_TSMM_IMPL`` override (pallas | pallas_interpret | xla |
    auto).  See :func:`variant_choice` for the kernel-variant analogue."""
    return os.environ.get("REPRO_TSMM_IMPL", "auto")


def variant_choice() -> Optional[KernelSpec]:
    """``REPRO_TSMM_VARIANT`` override — force a named kernel variant on
    every planned TSMM for debugging/bisection (DESIGN.md §10).

    Syntax: ``name`` or ``name:key=val,key2=val2`` — e.g. ``ksplit`` or
    ``ksplit:splits=4``.  Raises ``ValueError`` listing the registered
    variants on an unknown name, so a typo fails loudly instead of
    silently serving the baseline.  An orientation-specific variant
    (kmajor, b_resident, epilogue_split, fused_pack) only overrides the
    matmuls of its own regime — a real model run exercises both regimes,
    so the other one keeps its planned kernel."""
    raw = os.environ.get("REPRO_TSMM_VARIANT", "")
    if not raw:
        return None
    return variants.parse_spec(raw)


def schedule_choice() -> Optional[ScheduleSpec]:
    """``REPRO_TSMM_SCHEDULE`` override — force a grid schedule on every
    planned TSMM for debugging/bisection (DESIGN.md §11).

    Syntax: ``m_split=2,multibuffer=3,dims=parallel;arbitrary`` (any
    subset of fields).  Unknown fields or bad semantics names raise, so a
    typo fails loudly instead of silently serving the default schedule.
    Kernels clamp knobs they cannot express at the current shape (an
    M-partition that does not divide the row-panel count degrades to the
    nearest divisor; a dims override of the wrong rank falls back to the
    kernel's default semantics)."""
    raw = os.environ.get("REPRO_TSMM_SCHEDULE", "")
    if not raw:
        return None
    return parse_schedule(raw)


def _override_spec(spec: KernelSpec, override: Optional[KernelSpec],
                   orientation: str) -> KernelSpec:
    if override is not None and variants.applies_to(override, orientation):
        return override
    return spec


def _stamped_spec(b: PackedTensor, m: int) -> tuple:
    """The (kernel spec, schedule) ``prepack_for`` stamped on the packed
    weight for the smallest batch bucket covering ``m`` ((None, None)
    when unstamped or past the largest bucket — callers fall through to
    the registry).  Entries stamped before the schedule axis existed are
    (bucket, spec) pairs and decode to the default schedule."""
    for entry in getattr(b, "kernel_specs", ()):
        if entry[0] >= m:
            sched = entry[2] if len(entry) > 2 else ScheduleSpec()
            return entry[1], sched
    return None, None


def tsmm_dot(a, b, *, bias=None, act: Optional[str] = None,
             plan: Optional[Plan] = None, impl: Optional[str] = None,
             name: Optional[str] = None):
    """C = act(A @ B + bias) with TSMM planning.

    ``a``: (..., k) activations; ``b``: (k, n) array or PackedTensor.
    Shapes are static under jit, so planning happens at trace time — the
    'runtime stage' of the paper runs once per compiled program.
    ``name`` names the planned kernel's custom call in the program.
    """
    impl = impl or impl_choice()
    override = variant_choice()
    sched_override = schedule_choice()
    lead, k = a.shape[:-1], a.shape[-1]
    m = 1
    for d in lead:
        m *= d
    a2 = a.reshape(m, k)

    if is_packed(b):
        nk, _, bk, bn = b.blocks.shape[-4:]
        if k == nk * bk:
            # 2D-TP serving: k-shard the skinny activation panel to match
            # the weight's row-block sharding -> partial sums + psum of the
            # (tiny) output instead of gathering the (huge) packed weight.
            from repro.sharding.context import shard_act
            a2 = shard_act(a2.reshape(m, nk, bk), "batch", "kblocks", None
                           ).reshape(m, k)
        spec = plan.kernel if plan is not None else None
        sched = plan.schedule if plan is not None else None
        if spec is None:
            # serving replay of the registry's recorded winner: the
            # variant + schedule chosen when the weight was packed are
            # stamped on the PackedTensor (num_shards/dtype-proof —
            # prepack_for keyed the tuned problems correctly, whatever
            # the sharding)...
            spec, sched = _stamped_spec(b, m)
        if spec is None:
            # ...and a manually packed tensor falls back to a registry
            # peek (non-mutating, so the engine's miss telemetry stays
            # honest); an uncovered shape serves the baseline.
            cached = registry.peek(
                Problem(m, k, b.orig_cols, str(a.dtype)).key())
            spec = cached.kernel if cached is not None else variants.BASELINE
            sched = cached.schedule if cached is not None else None
        spec = _override_spec(spec, override, "skinny_a")
        sched = sched_override or sched

        def _packed(use_impl):
            def run(x, w, bias_, act_, layer):
                return variants.run_skinny_a(
                    spec, x, w, bias_, act_, bk=bk, bn=bn, packed=True,
                    impl=use_impl, schedule=sched, name=name, layer=layer)
            mesh = _mosaic_mesh(use_impl)
            if mesh is not None:
                out = _skinny_per_shard(mesh, b, run, a2, bias, act)
            else:
                out = run(a2, b.blocks, bias, act, b.layer)
            return out[:, : b.orig_cols]

        out = _laddered(
            "skinny", f"skinny_a/{m}x{k}x{b.orig_cols}/{spec.key()}",
            lambda: _packed(impl),
            lambda: _packed("xla"),
            lambda: _gemm_epilogue(a2, b.unpack(), bias, act, a.dtype))
        return out.reshape(*lead, b.orig_cols)

    n = b.shape[-1]
    if plan is None and is_tsmm(m, k, n) and _mosaic_mesh(impl) is None:
        # (under a mesh a weight left unpacked serves as a plain GEMM,
        # which XLA partitions; a Mosaic kernel would need its sharding)
        plan = plan_for_matmul(m, k, n, str(a.dtype))
    if plan is not None and plan.orientation == "skinny_a":
        spec = _override_spec(plan.kernel, override, "skinny_a")
        sched = sched_override or plan.schedule

        def _skinny(use_impl):
            return variants.run_skinny_a(
                spec, a2, b, bias, act, bk=plan.bk, bn=plan.bn,
                packed=False, impl=use_impl, schedule=sched,
                name=name)[:, :n]

        out = _laddered(
            "skinny", f"skinny_a/{m}x{k}x{n}/{spec.key()}",
            lambda: _skinny(impl),
            lambda: _skinny("xla"),
            lambda: _gemm_epilogue(a2, b, bias, act, a.dtype))
        return out.reshape(*lead, n)
    if plan is not None and plan.orientation == "tall_a":
        # bias/activation fuse into the variant's epilogue (DESIGN.md
        # §11): the prefill path executes act(A@B + bias) in ONE kernel —
        # no post-hoc pass, no extra (m, n) round trip over HBM
        spec = _override_spec(plan.kernel, override, "tall_a")
        sched = sched_override or plan.schedule

        def _tall(use_impl):
            if plan.prepack:
                ap = pack(a2, plan.bm, plan.bk)
                return variants.run_tall_a(
                    spec, ap.blocks, b, bias, act, bm=plan.bm, bk=plan.bk,
                    packed=True, impl=use_impl, schedule=sched,
                    name=name)[:m, :n]
            return variants.run_tall_a(
                spec, a2, b, bias, act, bm=plan.bm, bk=plan.bk,
                packed=False, impl=use_impl, schedule=sched, name=name)

        out = _laddered(
            "tall", f"tall_a/{m}x{k}x{n}/{spec.key()}",
            lambda: _tall(impl),
            lambda: _tall("xla"),
            lambda: _gemm_epilogue(a2, b, bias, act, a.dtype))
        return out.reshape(*lead, n)
    # unplanned fallback: accumulate in f32 like every planned path
    # (ops.tsmm* all pass preferred_element_type) so bf16 results do not
    # depend on whether a plan existed for the shape.  This is the ONLY
    # path left with a post-hoc epilogue — XLA fuses it into the dot's
    # consumer within the surrounding jit, and non-TSMM shapes are
    # compute-bound anyway (DESIGN.md §2).
    return _gemm_epilogue(a2, b, bias, act, a.dtype).reshape(*lead, n)


def prepack_for(m_skinny, w, *, num_shards: int = 1,
                shard_divisors: tuple = (1, 1),
                hw: Optional[HwSpec] = None) -> Optional[PackedTensor]:
    """Plan + pack a weight for decode-time reuse.

    ``m_skinny`` is one serving batch size or a tuple of batch buckets
    (DESIGN.md §7).  With multiple buckets ONE packed layout serves every
    bucket: the block shape is chosen from the intersection of conforming
    blocks — (bk, bn) that divide the per-shard dims AND fit the VMEM
    budget for every bucket's problem — ranked by the vmem model's
    predicted time summed across buckets.

    ``shard_divisors`` = (row_shards, col_shards) the weight is distributed
    over; chosen blocks must divide the per-shard dims so packing commutes
    with sharding (pack happens locally on each device's shard).
    Returns None when no conforming block exists (caller keeps the plain
    weight; honest fallback, recorded by the caller).
    """
    hw = hw or default_hw()
    buckets = (m_skinny,) if isinstance(m_skinny, int) else tuple(m_skinny)
    k, n = int(w.shape[-2]), int(w.shape[-1])
    rs, cs = shard_divisors
    if k % rs or n % cs:
        return None
    ks, ns = k // rs, n // cs
    # per-bucket plans (registry-backed: after the install sweep this is a
    # pure lookup; on a cold registry the tuned plans stay in memory and
    # the caller flushes once per tree, not once per leaf); buckets whose
    # problem is not TSMM-shaped get an untuned Problem so feasibility is
    # still enforced for them.
    pset = make_plan_set(ks, ns, buckets, str(w.dtype), num_shards, hw,
                         persist=False)
    problems = [pset.plans[m].problem if m in pset.plans
                else Problem(m, ks, ns, str(w.dtype), num_shards)
                for m in buckets]
    # the tuned plans bound the block search: no bucket wants blocks
    # beyond its tuned (bk, bn), so the conforming search is capped at
    # the largest tuned preference across buckets
    caps = (max((pl.bk for pl in pset.plans.values()), default=None),
            max((pl.bn for pl in pset.plans.values()), default=None))
    chosen = _conforming_blocks(problems, ks, ns, hw, caps=caps)
    if chosen is None:
        return None
    pk = pack(w, *chosen)
    # stamp the per-bucket kernel variants + grid schedules on the packed
    # weight so the decode path replays exactly what was tuned
    # (DESIGN.md §10/§11) — the registry key is shard/dtype-specific, but
    # the stamp travels with the weight.  Each (spec, schedule) is
    # RE-GATED at the conforming blocks the tensor was actually packed
    # with (which may differ from the blocks the plan was tuned at): an
    # infeasible or prepack=False-only variant falls back to the
    # baseline, an infeasible schedule (e.g. the multibuffer footprint
    # blown at the bigger block) to the default, instead of replaying a
    # program that was never validated at this layout.
    pk.kernel_specs = tuple(sorted(
        (m, *_stamp_spec_for_blocks(pset.plans[m], *chosen, hw=hw))
        for m in pset.plans))
    return pk


def _stamp_spec_for_blocks(plan: Plan, bk: int, bn: int, *,
                           hw: Optional[HwSpec] = None) -> tuple:
    """``plan``'s tuned (kernel variant, schedule), re-validated for a
    PACKED weight with blocks (bk, bn): a spec with no packed-path
    applicability (fused_pack — there is no per-call pack left to fuse)
    or one that is infeasible at these blocks (e.g. a k-split that no
    longer divides the k-block count, or VMEM blown at the bigger block)
    degrades to the baseline; an infeasible schedule degrades to the
    default, both of which are always valid."""
    hw = hw or default_hw()
    spec, sched = plan.kernel, plan.schedule
    if not spec.is_baseline:
        try:
            g = variants.from_kernel_spec(spec)
        except ValueError:
            g = None
        if g is None or not variants.grammar.valid(g, "skinny_a", True):
            # not emittable against a prepacked skinny weight (tall-only
            # point, or a pack-fusing point with no per-call pack left)
            spec = KernelSpec()
    trial = dataclasses.replace(plan, bk=bk, bn=bn, prepack=True,
                                kernel=spec)
    if not feasible(trial, hw):
        # the schedule may be the only blown gate at these blocks — shed
        # it first, then the variant (the conforming-block search
        # guaranteed baseline+default feasibility)
        sched = ScheduleSpec()
        trial = dataclasses.replace(trial, schedule=sched)
        if not feasible(trial, hw):
            spec = KernelSpec()
    return spec, sched


def _conforming_blocks(problems, ks: int, ns: int, hw: HwSpec = TPU_V5E,
                       caps: tuple = (None, None)) -> Optional[tuple]:
    """Best (bk, bn) conforming for EVERY problem: multiples of 128 that
    divide the per-shard dims (within the tuned ``caps``, when given),
    VMEM-feasible for all buckets, minimal predicted time summed across
    buckets."""
    cap_bk = min(ks, caps[0]) if caps[0] else ks
    cap_bn = min(ns, caps[1]) if caps[1] else ns
    bks = [d for d in range(128, max(cap_bk, 128) + 1, 128) if ks % d == 0]
    bns = [d for d in range(128, max(cap_bn, 128) + 1, 128) if ns % d == 0]
    best, best_score = None, None
    for bk in bks:
        for bn in bns:
            trial = [Plan(p, "skinny_a", bm=p.m, bk=bk, bn=bn)
                     for p in problems]
            if not all(feasible(t, hw) for t in trial):
                continue
            score = sum(predict(t, hw).score for t in trial)
            if best_score is None or score < best_score:
                best, best_score = (bk, bn), score
    return best


# ---------------------------------------------------------------------------
# Distributed TSMM (shard_map) — the mesh-scale multi-thread optimizer
# ---------------------------------------------------------------------------


def distributed_tsmm(a, b, mesh: Mesh, axis: str = "data", *,
                     plan: Optional[Plan] = None, impl: Optional[str] = None):
    """Tall-A TSMM with the tall dim sharded over ``axis``; B replicated.

    Zero collectives in the compute path — the paper's GEBB_t property.
    A: (M, K) with M % mesh.shape[axis] == 0;  B: (K, N) skinny.
    """
    m, k = a.shape
    n = b.shape[1]
    shards = mesh.shape[axis]
    local_plan = plan or make_plan(
        Problem(m // shards, k, n, str(a.dtype), shards))

    def local(a_blk, b_full):
        if local_plan.prepack:
            ap = pack(a_blk, local_plan.bm, local_plan.bk)
            return ops.tsmm_packed(ap.blocks, b_full, impl=impl)[: a_blk.shape[0]]
        return ops.tsmm(a_blk, b_full, bm=local_plan.bm, bk=local_plan.bk,
                        impl=impl)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(axis, None), P(None, None)),
                       out_specs=P(axis, None))
    return fn(a, b)


def conventional_ksplit(a, b, mesh: Mesh, axis: str = "data", *,
                        impl: Optional[str] = None):
    """Conventional-library decomposition: contraction dim split over the
    mesh, partial products all-reduced.  The baseline the paper beats."""
    def local(a_blk, b_blk):
        part = jnp.dot(a_blk, b_blk, preferred_element_type=jnp.float32)
        return jax.lax.psum(part, axis).astype(a_blk.dtype)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(None, axis), P(axis, None)),
                       out_specs=P(None, None))
    return fn(a, b)


def overlapped_ring_tsmm(a, b, mesh: Mesh, axis: str = "data", *,
                         impl: Optional[str] = None):
    """Beyond-paper: ring-pipelined TSMM for the case where A arrives
    k-sharded (e.g. produced by an upstream TP layer) but we still want
    the no-n-split output layout.  Each step multiplies the resident A
    shard while ``ppermute``-ing the next one — collective/compute overlap
    instead of a blocking all-gather.

    A: (M, K) k-sharded over ``axis``; B: (K, N) k-sharded. Out: (M, N)
    row-sharded... returns replicated (M, N) partial-sum-free result.
    """
    shards = mesh.shape[axis]

    def local(a_blk, b_blk):
        # a_blk: (M, K/s) local; b_blk: (K/s, N) local
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % shards) for i in range(shards)]

        def step(carry, _):
            acc, a_cur, b_cur = carry
            acc = acc + jnp.dot(a_cur, b_cur, preferred_element_type=jnp.float32)
            a_nxt = jax.lax.ppermute(a_cur, axis, perm)
            b_nxt = jax.lax.ppermute(b_cur, axis, perm)
            return (acc, a_nxt, b_nxt), None

        acc = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)
        (acc, _, _), _ = jax.lax.scan(step, (acc, a_blk, b_blk), None,
                                      length=shards)
        return acc.astype(a_blk.dtype)

    # the VMA type system cannot prove the output replicated (the ring
    # accumulation makes it so only after all steps): unchecked
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(None, axis), P(axis, None)),
                       out_specs=P(None, None), check_vma=False)
    return fn(a, b)
