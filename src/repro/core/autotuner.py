"""The auto-tuner: install-time kernel selection + runtime plan generation.

Mirrors the paper's two stages:

* **install-time** — enumerate candidate inner-kernel block shapes, filter
  by the VMEM predictive model (Eq.2/3 analogue), rank.  On real TPU the
  performance evaluator then measures the short-list; in this container the
  evaluator runs in ``model`` mode (analytic) or ``wallclock`` mode against
  the blocked-XLA implementation (exercised in tests).
* **runtime** — given a concrete Problem, produce/lookup the execution
  Plan.  Two search patterns, straight from the paper §IV-A-1:
  pattern A searches downward from the VMEM bound in inner-kernel-sized
  steps; pattern B takes the largest power of two under the bound.

The measured path is an **adaptive short-list search** (DESIGN.md §9):
candidates are pruned by the (optionally calibrated) predictive model,
then measured in rank order with cached-measurement reuse, stopping
early once the wall-clock leader has survived ``stable`` consecutive
challengers — the model proposes, the stopwatch disposes.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Optional

from repro.core import registry
from repro.core.hw import TPU_V5E, HwSpec, spec_for_device_kind
from repro.core.plan import (SKINNY_MAX, BucketGrid, Plan, PlanGrid, PlanSet,
                             Problem, is_tsmm, schedules_for)
from repro.core.vmem_model import feasible, predict

log = logging.getLogger(__name__)

# The hardware model trace-time planning ranks against, resolved on first
# use (see ``default_hw``).  The serving engine swaps in a calibrated spec
# (fitted from the measurement cache) so registry misses inside jit
# traces are ranked by measured reality, not the datasheet — the
# "measure -> model -> plan" loop closed.
_DEFAULT_HW: Optional[HwSpec] = None


def default_hw() -> HwSpec:
    """The planning spec.  On a TPU it is the spec of the attached chip's
    ``device_kind`` (an unknown kind raises).  Every other backend plans
    against the v5e model: CPU runs check plans and control flow for the
    chip this repository targets, they have no VMEM of their own."""
    global _DEFAULT_HW
    if _DEFAULT_HW is None:
        import jax
        if jax.default_backend() == "tpu":
            _DEFAULT_HW = spec_for_device_kind(jax.devices()[0].device_kind)
        else:
            _DEFAULT_HW = TPU_V5E
    return _DEFAULT_HW


def set_default_hw(hw: HwSpec) -> HwSpec:
    """Install ``hw`` as the planning default; returns the previous one."""
    global _DEFAULT_HW
    prev, _DEFAULT_HW = _DEFAULT_HW, hw
    return prev


def _pow2_below(x: int) -> int:
    p = 1
    while p * 2 <= x:
        p *= 2
    return p


def _ceil_to(x: int, q: int) -> int:
    return -(-x // q) * q


def candidate_blocks(problem: Problem,
                     hw: Optional[HwSpec] = None) -> list[Plan]:
    """Enumerate feasible candidate plans for one problem.

    The search space is the cross product of block shapes x the kernel
    synthesis grammar's enumerable points (kernels/variants/grammar,
    DESIGN.md §10, §14) x grid schedules (DESIGN.md §11) — the paper's
    install-time selection among competing inner kernels AND among
    partitionings/pipelinings of each kernel, with the kernel family now
    GENERATED rather than hand-registered.  Candidates are model-ranked
    (the calibrated predictive model is the prune); the measured
    tournament then times whichever grammar points/schedules survive."""
    from repro.kernels.variants import specs_for  # lazy: jax-free grammar
    hw = hw or default_hw()
    orientation = "tall_a" if problem.skinny_dim == "n" else "skinny_a"
    sl = hw.sublane.get(problem.dtype, 8)
    cands: list[Plan] = []

    if orientation == "tall_a":
        n_pad = _ceil_to(problem.n, 128)
        # pattern B: powers of two; pattern A: near-bound multiples of the
        # MXU edge (the paper's [bound - 8x, bound] walk).
        bms = {256, 512, 1024, 2048, 4096, _pow2_below(max(problem.m, sl))}
        bks = {128, 256, 512, 1024, 2048, _pow2_below(max(problem.k, 128))}
        for bm in sorted(bms):
            for bk in sorted(bks):
                if bm > max(problem.m, sl) or bk > max(problem.k, 128):
                    continue
                cands.append(Plan(problem, "tall_a", bm=bm, bk=bk, bn=n_pad))
    else:
        bns = {128, 256, 512, 1024, 2048}
        bks = {128, 256, 512, 1024, 2048, _pow2_below(max(problem.k, 128))}
        for bn in sorted(bns):
            for bk in sorted(bks):
                if bn > _ceil_to(problem.n, 128) or bk > max(problem.k, 128):
                    continue
                cands.append(Plan(problem, "skinny_a", bm=problem.m, bk=bk, bn=bn))

    # kernel axis: every block candidate x every grammar point emittable
    # for its (orientation, prepack); baseline-first spec order keeps
    # ties deterministic under the stable sort below
    expanded = []
    for c in cands:
        for spec in specs_for(c.orientation, c.prepack):
            expanded.append(
                c if spec == c.kernel else dataclasses.replace(c, kernel=spec))
        if c.orientation == "skinny_a" and c.prepack:
            # the natural-weight call path re-packs per call: model it as
            # a prepack=False sibling so pack-on-the-fly variants
            # (fused_pack) compete — the model charges every re-packing
            # prepack=False candidate the per-call pack traffic, so these
            # never outrank their prepack=True twins on ties (they are
            # appended after, and the sort below is stable)
            cf = dataclasses.replace(c, prepack=False)
            for spec in specs_for("skinny_a", prepack=False):
                expanded.append(dataclasses.replace(cf, kernel=spec))

    # grid-schedule axis (DESIGN.md §11): every (block, point) candidate
    # x every schedule its kernel supports — default-schedule first per
    # candidate, so ties under the stable sort keep pre-schedule behavior
    scheduled = []
    for c in expanded:
        for sched in schedules_for(c.orientation, c.kernel):
            scheduled.append(
                c if sched.is_default
                else dataclasses.replace(c, schedule=sched))

    out = [predict(c, hw) for c in scheduled if feasible(c, hw)]
    out.sort(key=lambda p: p.score)
    return out


def _transfer_candidates(problem: Problem, hw: HwSpec,
                         reg=None) -> list[Plan]:
    """Winner-transfer warm start (DESIGN.md §14): the measured winners
    of the NEIGHBORING bucket shapes (m/2 and 2m, same k/n/dtype), rebased
    onto this problem.  Tall-and-skinny winners are stable across the
    token-bucket ladder far more often than not, so seeding the
    tournament with them lets a transferred champion win in one
    measurement instead of re-searching the grammar from scratch.  Only
    MEASURED neighbors transfer (a model-ranked neighbor adds nothing the
    model prune doesn't already know); infeasible rebases are dropped."""
    reg = reg if reg is not None else registry.default()
    out = []
    for m2 in (problem.m // 2, problem.m * 2):
        if m2 < 1 or m2 == problem.m:
            continue
        near = registry.get(dataclasses.replace(problem, m=m2).key())
        if near is None or near.chosen_by != "measured":
            continue
        cand = dataclasses.replace(
            near, problem=problem, chosen_by="model", score=0.0,
            t_compute=0.0, t_memory=0.0)
        if cand.orientation == "skinny_a":
            cand = dataclasses.replace(cand, bm=problem.m)
        if feasible(cand, hw):
            out.append(predict(cand, hw))
    return out


def measure_short_list(cands: list, *, top_k: int, stable: int,
                       iters: int, warmup: int) -> Plan:
    """Tournament evaluator stage (DESIGN.md §9, §14): the model-ranked
    short-list is measured in order — cached records replay for free —
    with the wall-clock leader defending against each challenger; the
    tournament ends once the leader has beaten ``stable`` challengers in
    a row (the grammar makes the full space too large to time, so the
    calibrated model prunes and the stopwatch arbitrates the rest)."""
    from repro.core.evaluator import measure_plan  # lazy: avoids cycle
    reg = registry.default()
    best, best_rec, streak, tried = None, None, 0, 0
    for plan in cands[:max(top_k, 1)]:
        rec = reg.lookup_measurement(plan)
        if rec is None:
            rec = measure_plan(plan, warmup=warmup, iters=iters, reg=reg,
                               source="autotuner")
        tried += 1
        if best_rec is None or rec.seconds < best_rec.seconds:
            best, best_rec, streak = plan, rec, 0
        else:
            streak += 1
        if tried >= 2 and streak >= stable:
            break
    log.info("evaluator: measured %d/%d candidates (leader stable after %d)",
             tried, len(cands), streak)
    return dataclasses.replace(best, score=best_rec.seconds,
                               chosen_by="measured")


# original private name (pre-fleet-service callers)
_measure_short_list = measure_short_list


def make_plan(
    problem: Problem,
    hw: Optional[HwSpec] = None,
    *,
    measure: Optional[str] = None,   # None -> model only; "wallclock" -> evaluate
    top_k: int = 3,
    stable: int = 2,
    iters: int = 5,
    warmup: int = 2,
    persist: bool = True,
    impl: str = "auto",
    force: bool = False,
) -> Plan:
    """Runtime-stage entry: cached plan or fresh tune.

    ``force`` skips the registry lookup and re-tunes (the calibrated
    re-rank pass and the background tuner) — the registry's provenance
    guard still keeps an existing measured winner over a model-ranked
    challenger, and ``put`` returns whichever plan actually stands."""
    hw = hw or default_hw()
    if not force:
        cached = registry.get(problem.key())
        if cached is not None:
            return cached

    cands = candidate_blocks(problem, hw)
    if not cands:
        # degenerate shapes: fall back to a single-block plan
        plan = predict(
            Plan(problem, "tall_a" if problem.skinny_dim == "n" else "skinny_a",
                 bm=max(problem.m, 8), bk=128, bn=_ceil_to(max(problem.n, 1), 128),
                 impl="xla", prepack=False),
            hw,
        )
        return registry.put(plan, persist=persist)

    if measure == "wallclock":
        # seed the tournament with measured winners transferred from the
        # neighboring bucket shapes (warm start), then the model ranking
        short = _transfer_candidates(problem, hw) + cands
        seen, deduped = set(), []
        for c in short:
            tk = c.tuning_key()
            if tk not in seen:
                seen.add(tk)
                deduped.append(c)
        best = _measure_short_list(deduped, top_k=top_k, stable=stable,
                                   iters=iters, warmup=warmup)
    else:
        best = cands[0]
    best = dataclasses.replace(best, impl=impl)
    best = registry.put(best, persist=persist)
    log.info("autotuned %s", best)
    return best


def plan_for_matmul(m: int, k: int, n: int, dtype: str = "bfloat16",
                    num_shards: int = 1, **kw) -> Optional[Plan]:
    """None if the shape is not tall-and-skinny (caller uses plain GEMM)."""
    if not is_tsmm(m, k, n):
        return None
    return make_plan(Problem(m, k, n, dtype, num_shards), **kw)


def make_plan_set(
    k: int,
    n: int,
    buckets: tuple,
    dtype: str = "bfloat16",
    num_shards: int = 1,
    hw: Optional[HwSpec] = None,
    *,
    measure: Optional[str] = None,
    persist: bool = True,
    impl: str = "auto",
    iters: int = 5,
    force: bool = False,
) -> PlanSet:
    """Per-bucket plans for one (k, n) weight shape (DESIGN.md §7).

    Each bucket m with a TSMM-shaped (m, k, n) gets its own Plan (cached
    in / restored from the registry); non-TSMM buckets are skipped — at
    runtime those fall back to plain GEMM.  With ``persist`` the set is
    written back in ONE registry write, and only if a lookup missed (a
    warm, all-hit call never rewrites the cache file).
    """
    misses_before = registry.stats()["misses"]
    plans = {}
    for m in buckets:
        if not is_tsmm(m, k, n):
            continue
        plans[m] = make_plan(Problem(m, k, n, dtype, num_shards), hw,
                             measure=measure, persist=False, impl=impl,
                             iters=iters, force=force)
    # force-mode re-tunes bypass the lookup, so the miss counter cannot
    # be the write trigger for them
    tuned = (force and plans) or registry.stats()["misses"] > misses_before
    if persist and tuned:
        registry.flush()
    return PlanSet(plans)


def make_plan_grid(
    k: int,
    n: int,
    grid: BucketGrid,
    dtype: str = "bfloat16",
    num_shards: int = 1,
    hw: Optional[HwSpec] = None,
    *,
    measure: Optional[str] = None,
    persist: bool = True,
    impl: str = "auto",
    iters: int = 5,
    force: bool = False,
) -> PlanGrid:
    """Per-cell prefill plans for one (k, n) shape over a 2D bucket grid
    (DESIGN.md §8).

    Cell (bb, lb) -> Plan for the (bb*lb, k, n) prefill problem; cells
    sharing a token count share one Plan (and one registry entry).  Like
    ``make_plan_set`` this is registry-backed and writes back at most once."""
    misses_before = registry.stats()["misses"]
    by_tokens = {}
    for m in grid.token_buckets():
        if not is_tsmm(m, k, n):
            continue
        by_tokens[m] = make_plan(Problem(m, k, n, dtype, num_shards), hw,
                                 measure=measure, persist=False, impl=impl,
                                 iters=iters, force=force)
    plans = {cell: by_tokens[cell[0] * cell[1]] for cell in grid.cells()
             if cell[0] * cell[1] in by_tokens}
    tuned = (force and by_tokens) or registry.stats()["misses"] > misses_before
    if persist and tuned:
        registry.flush()
    return PlanGrid(grid, plans)
