"""Performance evaluator — measures candidate plans and calibrates the model.

On TPU this times the Pallas kernels; on this CPU container it times the
blocked-XLA implementation (same math, same layout) so the measurement
machinery itself is exercised end-to-end.  Three jobs (DESIGN.md §9):

* **measure** — :func:`measure_plan` times the EXACT code path ``tsmm_dot``
  replays for the plan (including the per-call pack for non-pre-packed
  skinny plans), verifies the timed callable's output against the serving
  path (:func:`parity_check`), and records a :class:`MeasureRecord`
  (min-of-iters seconds, iteration count, dispersion, provenance) into the
  registry's persistent measurement cache;
* **calibrate** — :func:`fit_hw` least-squares the roofline coefficients
  (effective HBM bandwidth, MXU efficiency, per-grid-step overhead in
  ``HwSpec``) from cached measurements, so a handful of timings re-ranks
  EVERY problem in the grid, not just the measured shapes;
* **rank** — :func:`measure_plans` returns the measured winner for a
  short-list (the autotuner adds the adaptive early-stop loop on top).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing, registry
from repro.core.hw import TPU_V5E, HwSpec
from repro.core.plan import Plan
from repro.core.registry import MeasureRecord, Registry
from repro.core.vmem_model import features
from repro.kernels import ops, variants

# fit_hw needs at least this many cached records before it trusts a fit
MIN_FIT_RECORDS = 4
# efficiency assigned to a roofline term the active-set fit DROPPED
# (coefficient clamped to zero): effectively infinite, so predict()
# reproduces the fitted model's zero term instead of silently re-adding
# the datasheet value the fit rejected
DROPPED_TERM_EFFICIENCY = 1e9


def _materialize(plan: Plan, seed: int = 0):
    """Deterministic operands for one plan, drawn on the device: an
    lm-head-sized weight drawn on the host would take seconds per
    candidate."""
    p = plan.problem
    dt = jnp.dtype(p.dtype) if p.dtype != "bfloat16" else jnp.bfloat16
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (p.m, p.k), jnp.float32).astype(dt)
    b = jax.random.normal(kb, (p.k, p.n), jnp.float32).astype(dt)
    return a, b


def resolve_impl(impl: Optional[str]) -> str:
    if impl in (None, "auto"):
        return "xla" if jax.default_backend() != "tpu" else "pallas"
    return impl


def build_callable(plan: Plan, impl: Optional[str] = None) -> Callable:
    """A zero-arg callable executing the plan's serving path.

    Pre-pack cost placement mirrors what ``tsmm_dot`` actually replays:
    a ``prepack=True`` skinny plan serves from a load-time PackedTensor,
    so its pack stays OUTSIDE the timed region (the paper's Eq.7 'packing
    time is ignored' data-reuse case); a ``prepack=False`` skinny plan
    makes ``tsmm_dot`` pack the weight on every call, so the pack is
    timed too — previously both were timed as pre-packed, which made
    prepack=False candidates look free.  Tall-A activations are packed
    per call by ``tsmm_dot`` as well, but that operand IS the streamed
    input; the model amortizes it (Eq.7) and we keep it outside the
    region for both variants so tall-A candidates stay comparable.

    Kernel-variant + schedule fidelity (DESIGN.md §10/§11): the callable
    dispatches through ``kernels.variants.run_*`` with the plan's
    ``kernel`` spec AND its ``schedule`` — the SAME registry entry point
    ``tsmm_dot`` replays at serving time — so the stopwatch times exactly
    the fused variant/grid-schedule the plan records."""
    p = plan.problem
    a, b = _materialize(plan)
    impl = resolve_impl(impl)
    spec = plan.kernel
    sched = plan.schedule
    if plan.orientation == "tall_a":
        if plan.prepack:
            ap = jax.block_until_ready(ops.pack_blocks(a, plan.bm, plan.bk))
            return lambda: variants.run_tall_a(spec, ap, b, bm=plan.bm,
                                               bk=plan.bk, packed=True,
                                               impl=impl, schedule=sched)
        return lambda: variants.run_tall_a(spec, a, b, bm=plan.bm,
                                           bk=plan.bk, packed=False,
                                           impl=impl, schedule=sched)
    if plan.prepack:
        wp = jax.block_until_ready(ops.pack_blocks(b, plan.bk, plan.bn))
        return lambda: variants.run_skinny_a(spec, a, wp, bk=plan.bk,
                                             bn=plan.bn, packed=True,
                                             impl=impl, schedule=sched)
    # tsmm_dot re-packs an unpacked skinny weight every call: the variant
    # owns that per-call cost (fused_pack skips it) — time it.
    return lambda: variants.run_skinny_a(spec, a, b, bk=plan.bk, bn=plan.bn,
                                         packed=False, impl=impl,
                                         schedule=sched)


def parity_check(plan: Plan, impl: Optional[str] = None,
                 rtol: float = 1e-2, atol: float = 1e-2,
                 fn: Optional[Callable] = None) -> None:
    """Assert the timed callable's output matches the plan's serving-path
    output (``tsmm_dot`` replaying the same plan on the same operands).
    Guards the measurement path against drifting from what serving runs —
    a fast wrong kernel must never win the evaluator.  ``fn`` lets the
    caller pass the callable it is about to time (operands are
    deterministic per plan, so both sides see the same data)."""
    from repro.core.tsmm import tsmm_dot  # lazy: avoids import cycle
    p = plan.problem
    a, b = _materialize(plan)
    rimpl = resolve_impl(impl)
    fn = fn or build_callable(plan, impl)
    timed = np.asarray(jax.block_until_ready(fn()),
                       np.float32)[:p.m, :p.n]
    if plan.orientation == "skinny_a" and plan.prepack:
        # packed serving path; the explicit plan pins the kernel variant
        # (a candidate under measurement is not in the registry yet)
        served = tsmm_dot(a, packing.pack(b, plan.bk, plan.bn), plan=plan,
                          impl=rimpl)
    else:
        served = tsmm_dot(a, b, plan=plan, impl=rimpl)
    served = np.asarray(served, np.float32)[:p.m, :p.n]
    if not np.allclose(timed, served, rtol=rtol, atol=atol):
        err = float(np.max(np.abs(timed - served)))
        raise AssertionError(
            f"evaluator/serving parity failure for {plan}: timed callable "
            f"diverges from tsmm_dot replay (max abs err {err:.3e})")


def time_samples(fn: Callable, *, warmup: int = 2, iters: int = 5) -> list:
    """Raw per-call wall-clock samples after warmup — THE shared timing
    loop: the measurement path below and ``benchmarks/common.timeit`` both
    use it, so benchmark tables and install-time measurements are computed
    from the same estimator (min-of-iters; see :func:`measure_plan`)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return ts


_time_samples = time_samples  # original private name (internal callers)


def time_callable(fn: Callable, *, warmup: int = 2, iters: int = 5) -> float:
    """Median seconds per call."""
    return float(np.median(_time_samples(fn, warmup=warmup, iters=iters)))


def measure_plan(plan: Plan, impl: Optional[str] = None, *,
                 warmup: int = 2, iters: int = 5, check: bool = True,
                 reg: Optional[Registry] = None,
                 source: str = "evaluator") -> MeasureRecord:
    """Time one plan (with parity verification) and cache the record.

    ``seconds`` is the FASTEST of the timed calls: scheduling noise on a
    shared machine is strictly additive (a sample is never faster than
    the kernel), so the min is the stable estimator of the kernel's own
    cost — the median of a handful of samples can land on a contention
    spike and invert a 5x real difference between plans.  ``dispersion``
    (IQR over min) records how noisy the samples were."""
    fn = build_callable(plan, impl)
    if check:
        parity_check(plan, impl, fn=fn)
    ts = _time_samples(fn, warmup=warmup, iters=iters)
    best = float(np.min(ts))
    q25, q75 = np.percentile(ts, (25, 75))
    rec = MeasureRecord(plan=plan, seconds=best, iters=iters,
                        dispersion=float((q75 - q25) / max(best, 1e-12)),
                        impl=resolve_impl(impl), source=source,
                        wall_time=time.time())
    (reg or registry.default()).record_measurement(rec)
    return rec


def measure_plans(plans: list, impl: Optional[str] = None,
                  warmup: int = 2, iters: int = 5, *, check: bool = True,
                  reuse: bool = True, reg: Optional[Registry] = None,
                  source: str = "evaluator") -> Plan:
    """Time each candidate, return the winner with measured score.

    ``reuse`` consults the persistent measurement cache first, so a
    repeated install sweep only pays for plans it has never timed."""
    if not plans:
        raise ValueError("measure_plans needs at least one candidate plan")
    reg = reg or registry.default()
    best, best_rec = None, None
    for plan in plans:
        rec = reg.lookup_measurement(plan) if reuse else None
        if rec is None:
            rec = measure_plan(plan, impl, warmup=warmup, iters=iters,
                               check=check, reg=reg, source=source)
        if best_rec is None or rec.seconds < best_rec.seconds:
            best, best_rec = plan, rec
    return dataclasses.replace(best, score=best_rec.seconds,
                               chosen_by="measured")


def measure_plans_interleaved(plans: list, impl: Optional[str] = None, *,
                              rounds: int = 4, warmup: int = 2,
                              check: bool = True,
                              reg: Optional[Registry] = None,
                              source: str = "evaluator") -> list:
    """Time a candidate set ROUND-ROBIN and return one record per plan.

    Timing candidates one after another lets machine drift (thermal,
    co-tenant load) land entirely on whichever plan happens to be
    running and silently reorder the short-list; interleaving the
    samples spreads any drift over every candidate equally, and the
    per-candidate min then estimates each kernel's own cost under the
    same conditions.  Use this when comparing candidates; use
    :func:`measure_plan` for one-off timings."""
    if not plans:
        return []
    reg = reg or registry.default()
    fns = [build_callable(p, impl) for p in plans]
    if check:
        for plan, fn in zip(plans, fns):
            parity_check(plan, impl, fn=fn)
    for fn in fns:
        for _ in range(warmup):
            jax.block_until_ready(fn())
    samples = [[] for _ in plans]
    for _ in range(max(rounds, 1)):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            samples[i].append(time.perf_counter() - t0)
    out = []
    for plan, ts in zip(plans, samples):
        best = float(np.min(ts))
        q25, q75 = np.percentile(ts, (25, 75))
        rec = MeasureRecord(plan=plan, seconds=best, iters=len(ts),
                            dispersion=float((q75 - q25) / max(best, 1e-12)),
                            impl=resolve_impl(impl), source=source,
                            wall_time=time.time())
        reg.record_measurement(rec)
        out.append(rec)
    return out


# ---------------------------------------------------------------------------
# Calibration: measurements -> fitted HwSpec (DESIGN.md §9)
# ---------------------------------------------------------------------------


def fit_hw(records: list, hw: HwSpec = TPU_V5E) -> HwSpec:
    """Least-squares the roofline coefficients from measurement records.

    Solves ``t_i ~= c_m * t_mem_i + c_c * t_cmp_i + oh * steps_i`` over
    the nominal-roofline features of each record's plan.  Rows are
    weighted by ``1/t_i`` (relative error): the cache holds microsecond
    decode shapes next to hundred-millisecond prefill shapes, and an
    unweighted fit would rank the small ones by the big ones' residuals.
    A one-pass active-set projection keeps coefficients non-negative;
    the map back is ``hbm_efficiency = 1/c_m``, ``mxu_efficiency =
    1/c_c``, ``grid_overhead_s = oh`` — a coefficient the projection
    dropped maps to ``DROPPED_TERM_EFFICIENCY`` so the calibrated spec
    reproduces the (term-free) model the fit actually solved.  Returns
    ``hw`` unchanged (uncalibrated) when there are fewer than
    ``MIN_FIT_RECORDS`` records or the design matrix is degenerate."""
    if len(records) < MIN_FIT_RECORDS:
        return hw
    A = np.asarray([features(r.plan, hw) for r in records], np.float64)
    t = np.asarray([r.seconds for r in records], np.float64)
    if (t <= 0).any():
        return hw
    W = A / t[:, None]                   # relative-error weighting
    ones = np.ones(len(t))
    free = [0, 1, 2]
    coefs = np.zeros(3)
    for _ in range(3):
        sub = W[:, free]
        if np.linalg.matrix_rank(sub) < len(free):
            return hw
        x, *_ = np.linalg.lstsq(sub, ones, rcond=None)
        if (x >= 0).all():
            for j, c in zip(free, x):
                coefs[j] = c
            break
        drop = free[int(np.argmin(x))]   # most-negative coefficient -> 0
        free = [j for j in free if j != drop]
        if not free:
            return hw
    else:
        return hw
    c_m, c_c, oh = coefs
    return dataclasses.replace(
        hw,
        hbm_efficiency=(1.0 / c_m) if c_m > 0 else DROPPED_TERM_EFFICIENCY,
        mxu_efficiency=(1.0 / c_c) if c_c > 0 else DROPPED_TERM_EFFICIENCY,
        grid_overhead_s=max(oh, 0.0),
        calibrated=True,
    )


def calibrated_hw(hw: HwSpec = TPU_V5E,
                  reg: Optional[Registry] = None) -> HwSpec:
    """Fit ``hw`` from the persistent measurement cache.  With too few
    records the nominal spec comes back (``.calibrated`` stays False)."""
    reg = reg or registry.default()
    return fit_hw(reg.measurements(), hw)


def spearman(a, b) -> float:
    """Spearman rank correlation (average ranks for ties; no scipy)."""
    def _ranks(x):
        x = np.asarray(x, np.float64)
        order = np.argsort(x, kind="stable")
        ranks = np.empty_like(x)
        ranks[order] = np.arange(len(x), dtype=np.float64)
        # average tied ranks so equal predictions don't fake correlation
        for v in np.unique(x):
            m = x == v
            ranks[m] = ranks[m].mean()
        return ranks
    ra, rb = _ranks(a), _ranks(b)
    sa, sb = ra.std(), rb.std()
    if sa == 0 or sb == 0:
        return 0.0
    return float(np.mean((ra - ra.mean()) * (rb - rb.mean())) / (sa * sb))
