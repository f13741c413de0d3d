"""Inner-kernel variant subsystem (DESIGN.md §10, §14).

Turns the inner kernel from a hard-coded function into a first-class,
enumerable, persisted tuning axis.  Since the generator refactor the
family is GENERATED, not registered: a :class:`KernelSpec` names one
point of the ``variants.grammar`` spec grammar (legacy PR-4 names are
aliases for their grammar points), ``specs_for`` renders the grammar
enumeration, and one parameterized Pallas emitter per orientation
(``kernels.gen``) lowers any valid point.  ``run_tall_a``/``run_skinny_a``
are the single dispatch points — ``core.tsmm.tsmm_dot`` (serving) and
``core.evaluator.build_callable`` (timing) both route through them, so
the evaluator times exactly the kernel serving replays.

This ``__init__`` imports only the jax-free spec/grammar modules; the
emitter module loads lazily the first time a spec is run.
"""

from __future__ import annotations

from repro.kernels.variants import grammar
from repro.kernels.variants.grammar import (GRAMMAR_VERSION, GenSpec,
                                            from_kernel_spec, to_kernel_spec)
from repro.kernels.variants.spec import (BASELINE, BASELINE_NAME, KernelSpec,
                                         legacy_specs_for, parse_spec,
                                         sampled_specs_for, specs_for,
                                         variant_names)

__all__ = [
    "BASELINE", "BASELINE_NAME", "GRAMMAR_VERSION", "GenSpec", "KernelSpec",
    "applies_to", "from_kernel_spec", "grammar", "legacy_specs_for",
    "parse_spec", "run_skinny_a", "run_tall_a", "sampled_specs_for",
    "specs_for", "to_kernel_spec", "variant_names", "verify_schedules",
    "verify_variants",
]


def applies_to(spec: KernelSpec, orientation: str) -> bool:
    """Whether ``spec``'s grammar point is emittable for ``orientation``
    (in at least one pre-packing regime) — the gate the
    REPRO_TSMM_VARIANT override uses so forcing an orientation-specific
    variant (kmajor, fused_pack, a ``gen:loop=kouter`` point, ...) only
    rebinds the matching regime instead of crashing the other.  Legacy
    names additionally stay pinned to the orientations PR 4 registered
    them for, keeping override semantics stable."""
    if spec.name not in grammar.LEGACY_ORIENTATIONS:
        raise ValueError(
            f"unknown kernel variant {spec.name!r}; registered variants: "
            f"{', '.join(variant_names())}")
    if orientation not in grammar.LEGACY_ORIENTATIONS[spec.name]:
        return False
    g = from_kernel_spec(spec)
    return (grammar.valid(g, orientation, True)
            or grammar.valid(g, orientation, False))


def run_tall_a(spec: KernelSpec, a, b, bias=None, act=None, *, bm: int = 0,
               bk: int = 0, packed: bool = False, impl=None, schedule=None,
               name=None):
    """Dispatch a tall-A matmul to the generator at ``spec``'s grammar
    point.

    ``a`` is natural (M, K) or pre-packed (nm, nk, bm, bk) per ``packed``
    (the caller owns the pack, mirroring the baseline's cost placement).
    ``bias``/``act`` fuse into the point's epilogue placement — the
    prefill path's act(A@B + bias) executes without a post-hoc (M, N)
    pass unless the point ASKS for one (``epi=split``), (DESIGN.md §11).
    ``schedule`` is the plan's ScheduleSpec (grid semantics / M
    partitioning / multibuffer depth); None = default.  ``name`` names
    the kernel in the compiled program (the leaf it serves).
    """
    if not applies_to(spec, "tall_a"):
        raise ValueError(f"kernel variant {spec.key()!r} has no tall_a "
                         f"implementation")
    from repro.kernels import gen
    return gen.emit_tall_a(from_kernel_spec(spec), a, b, bias, act, bm=bm,
                           bk=bk, packed=packed, impl=impl,
                           schedule=schedule, name=name)


def run_skinny_a(spec: KernelSpec, x, w, bias=None, act=None, *,
                 bk: int = 0, bn: int = 0, packed: bool = True, impl=None,
                 schedule=None, name=None, layer=None):
    """Dispatch a skinny-A (decode) matmul to the generator at ``spec``'s
    grammar point.

    ``w`` is the packed (nk, nn, bk, bn) blocks when ``packed`` else the
    natural (K, N) weight.  A pack-fusing point against an
    already-packed weight falls back to the baseline kernel inside the
    emitter (there is no pack left to fuse).  ``schedule`` and ``name``
    as in :func:`run_tall_a`; ``layer`` reads row ``layer`` of a
    layer-stacked packed ``w`` (L, nk, nn, bk, bn) inside the kernel.
    """
    if not applies_to(spec, "skinny_a"):
        raise ValueError(f"kernel variant {spec.key()!r} has no skinny_a "
                         f"implementation")
    from repro.kernels import gen
    return gen.emit_skinny_a(from_kernel_spec(spec), x, w, bias, act, bk=bk,
                             bn=bn, packed=packed, impl=impl,
                             schedule=schedule, name=name, layer=layer)


# ---------------------------------------------------------------------------
# grammar self-check (install --check / CI)
# ---------------------------------------------------------------------------


def verify_variants(impl: str = "pallas_interpret", *,
                    dtype: str = "float32", stride: int = 3) -> list:
    """Run a sampled set of grammar points — EVERY legacy-equivalent
    point plus every ``stride``-th novel ``gen`` point — on one tiny
    shape per regime and compare against the jnp reference.

    Returns a list of result dicts ``{spec, orientation, ok, error}`` —
    the install stage's ``--check`` fails the workflow when any entry has
    ``ok=False``, so an unemittable or numerically broken grammar point
    cannot reach a tuned registry.  ``impl='pallas_interpret'`` exercises
    the actual generated kernel bodies on CPU."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.dtype(dtype)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" else \
        dict(rtol=2e-4, atol=2e-4)
    rng = np.random.default_rng(0)

    def mk(shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           ).astype(dt)

    # one tiny problem per regime; blocks sized so every point's
    # constraints (k-split divisibility, VMEM residency) are exercised.
    # Tall-A verifies WITH a bias so the fused epilogue (DESIGN.md §11)
    # is exercised in every point's epilogue placement.
    a, bt = mk((256, 512)), mk((512, 8))          # tall: M=256, K=512, N=8
    x, w = mk((4, 512)), mk((512, 256))           # skinny: m=4, K=512, N=256
    bias = mk((256,))
    bias_t = mk((8,))
    want_tall = np.asarray(
        jnp.dot(a.astype(jnp.float32), bt.astype(jnp.float32))
        + bias_t.astype(jnp.float32)[None, :], np.float32)
    want_skinny = np.asarray(
        jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
        + bias.astype(jnp.float32)[None, :], np.float32)

    out = []
    for spec in sampled_specs_for("tall_a", stride=stride):
        row = {"spec": spec.key(), "orientation": "tall_a",
               "ok": True, "error": ""}
        try:
            for packed in (False, True):
                arg = ops.pack_blocks(a, 128, 128) if packed else a
                got = run_tall_a(spec, arg, bt, bias_t, bm=128, bk=128,
                                 packed=packed, impl=impl)
                np.testing.assert_allclose(
                    np.asarray(got, np.float32)[:256, :8], want_tall, **tol)
        except Exception as e:  # a broken point must not abort the sweep
            row["ok"] = False
            row["error"] = f"{type(e).__name__}: {e}"
        out.append(row)
    seen = set()
    for prepack in (True, False):
        for spec in sampled_specs_for("skinny_a", prepack, stride=stride):
            if spec.key() in seen:
                continue
            seen.add(spec.key())
            row = {"spec": spec.key(), "orientation": "skinny_a",
                   "ok": True, "error": ""}
            try:
                g = from_kernel_spec(spec)
                modes = (False,) if g.packfuse else (True, False)
                for packed in modes:
                    arg = ops.pack_blocks(w, 128, 128) if packed else w
                    got = run_skinny_a(spec, x, arg, bias, None, bk=128,
                                       bn=128, packed=packed, impl=impl)
                    np.testing.assert_allclose(
                        np.asarray(got, np.float32)[:4, :256], want_skinny,
                        **tol)
            except Exception as e:
                row["ok"] = False
                row["error"] = f"{type(e).__name__}: {e}"
            out.append(row)
    return out


def verify_schedules(impl: str = "pallas_interpret", *,
                     dtype: str = "float32") -> list:
    """Run EVERY enumerable grid schedule (DESIGN.md §11) against every
    legacy-equivalent grammar point (plus a couple of novel points) it
    applies to, on one tiny shape, and compare with the jnp reference —
    the schedule-axis analogue of :func:`verify_variants`, gated the
    same way by ``install --check``.

    Also exercises a dimension-semantics override (all-``arbitrary``),
    which every generated kernel must accept.  Returns result dicts
    ``{spec, schedule, orientation, ok, error}``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.plan import ScheduleSpec, schedules_for
    from repro.kernels import ops

    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.dtype(dtype)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" else \
        dict(rtol=2e-4, atol=2e-4)
    rng = np.random.default_rng(1)

    def mk(shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           ).astype(dt)

    # M=512/bm=128 -> 4 row panels, so m_split in {2, 4} divides evenly
    a, bt = mk((512, 512)), mk((512, 8))
    x, w = mk((4, 512)), mk((512, 256))
    bias_t, bias_s = mk((8,)), mk((256,))
    want_tall = np.asarray(
        jnp.dot(a.astype(jnp.float32), bt.astype(jnp.float32))
        + bias_t.astype(jnp.float32)[None, :], np.float32)
    want_skinny = np.asarray(
        jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
        + bias_s.astype(jnp.float32)[None, :], np.float32)

    def sampled(orientation, prepack=True):
        legacy = legacy_specs_for(orientation, prepack)
        novel = [s for s in specs_for(orientation, prepack)
                 if s.name == "gen"]
        return legacy + novel[:2]

    out = []
    for orientation in grammar.ORIENTATIONS:
        specs = sampled(orientation) if orientation == "tall_a" else \
            sampled(orientation, True) + [
                s for s in sampled(orientation, False)
                if from_kernel_spec(s).packfuse][:1]
        for spec in specs:
            g = from_kernel_spec(spec)
            scheds = list(schedules_for(orientation, spec))
            # dims / deeper multibuffer are not enumerated by the
            # autotuner (debugging knob; inexpressible on this Pallas)
            # but both are reachable via REPRO_TSMM_SCHEDULE: verify the
            # all-arbitrary override and an mb=3 schedule too (a
            # mismatched dims length falls back to default semantics)
            scheds.append(ScheduleSpec(dims=("arbitrary", "arbitrary")))
            if g.loop != "kouter":
                scheds.append(ScheduleSpec(multibuffer=3))
            for sched in scheds:
                row = {"spec": spec.key(), "schedule": sched.key(),
                       "orientation": orientation, "ok": True, "error": ""}
                try:
                    if orientation == "tall_a":
                        got = run_tall_a(spec, a, bt, bias_t, bm=128,
                                         bk=128, packed=False, impl=impl,
                                         schedule=sched)
                        np.testing.assert_allclose(
                            np.asarray(got, np.float32)[:512, :8],
                            want_tall, **tol)
                    else:
                        arg = w if g.packfuse else \
                            ops.pack_blocks(w, 128, 128)
                        got = run_skinny_a(spec, x, arg, bias_s, None,
                                           bk=128, bn=128,
                                           packed=not g.packfuse,
                                           impl=impl, schedule=sched)
                        np.testing.assert_allclose(
                            np.asarray(got, np.float32)[:4, :256],
                            want_skinny, **tol)
                except Exception as e:
                    row["ok"] = False
                    row["error"] = f"{type(e).__name__}: {e}"
                out.append(row)
    return out
