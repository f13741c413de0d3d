"""Kernel-variant subsystem (DESIGN.md §10): registry seeding, per-variant
numerical parity vs the jnp oracle (interpret + xla), Plan/KernelSpec
round-trip + old-registry back-compat, the REPRO_TSMM_VARIANT override,
the autotuner's variant x block search space, evaluator/serving variant
fidelity, and the k-split partial-sum property."""

import dataclasses
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import evaluator, registry
from repro.core.autotuner import candidate_blocks
from repro.core.plan import DEFAULT_SCHEDULE, Plan, Problem
from repro.core.vmem_model import contraction_steps, feasible, predict
from repro.kernels import ops, ref
from repro.kernels import variants
from repro.kernels.variants import (BASELINE, KernelSpec, parse_spec,
                                    run_skinny_a, run_tall_a,
                                    sampled_specs_for, specs_for,
                                    variant_names, verify_variants)

DATA = Path(__file__).parent / "data"
RNG = np.random.default_rng(7)


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans.json"))
    monkeypatch.setenv("REPRO_MEASURE_CACHE",
                       str(tmp_path / "measurements.json"))
    registry.clear_memory()
    yield tmp_path
    registry.clear_memory()


def _mk(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32)
                       ).astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# registry seeding + search-space growth
# ---------------------------------------------------------------------------


def test_registry_is_seeded_with_variant_family():
    names = set(variant_names())
    assert {"baseline", "ksplit", "kmajor", "b_resident",
            "epilogue_split", "fused_pack"} <= names
    # >= 4 variants per regime (the paper's inner-kernel family)
    assert len(specs_for("tall_a")) >= 4
    assert len(specs_for("skinny_a", prepack=True)) >= 4
    # fused_pack only applies where there is a per-call pack to fuse away
    pp_false = {s.name for s in specs_for("skinny_a", prepack=False)}
    pp_true = {s.name for s in specs_for("skinny_a", prepack=True)}
    assert "fused_pack" in pp_false and "fused_pack" not in pp_true
    # baseline enumerates first (deterministic tie-breaks in the tuner)
    assert specs_for("tall_a")[0] == BASELINE


def test_candidate_space_includes_variants():
    tall = candidate_blocks(Problem(8192, 4096, 16, "float32"))
    skinny = candidate_blocks(Problem(64, 4096, 4096, "float32"))
    assert len({p.kernel for p in tall}) >= 4
    assert len({p.kernel for p in skinny}) >= 4
    for p in tall + skinny:
        assert feasible(p)
    # the pack-on-the-fly variant is reachable: prepack=False siblings
    # are enumerated for the natural-weight skinny call path...
    assert any(p.kernel.name == "fused_pack" and not p.prepack
               for p in skinny)
    # ...but the model charges re-packing prepack=False candidates the
    # per-call pack, so the model-only winner stays a prepack=True plan
    assert skinny[0].prepack


def test_ksplit_feasibility_gate():
    prob = Problem(4096, 512, 16, "float32")
    base = Plan(prob, "tall_a", bm=256, bk=128, bn=128)
    ok = dataclasses.replace(base, kernel=KernelSpec.make("ksplit", splits=2))
    assert feasible(base) and feasible(ok)
    # 4 k-blocks cannot split 8 ways evenly -> infeasible, not wrong
    bad = dataclasses.replace(base, kernel=KernelSpec.make("ksplit", splits=8))
    assert not feasible(bad)
    # the split shortens the serial contraction chain the overhead term counts
    assert contraction_steps(ok) == contraction_steps(base) // 2


def test_variant_cost_terms_differ():
    """The per-variant traffic terms must actually move the model."""
    from repro.core.vmem_model import hbm_traffic_bytes
    prob = Problem(8192, 4096, 16, "float32")
    base = Plan(prob, "tall_a", bm=512, bk=512, bn=128)
    bres = dataclasses.replace(base, kernel=KernelSpec("b_resident"))
    ksp = dataclasses.replace(base, kernel=KernelSpec.make("ksplit", splits=2))
    assert hbm_traffic_bytes(bres) < hbm_traffic_bytes(base)  # no B reloads
    assert hbm_traffic_bytes(ksp) > hbm_traffic_bytes(base)   # partials traffic
    # fused_pack saves the per-call pack of a prepack=False skinny weight
    sp = Plan(Problem(64, 4096, 4096, "float32"), "skinny_a", bm=64,
              bk=512, bn=512, prepack=False)
    fused = dataclasses.replace(sp, kernel=KernelSpec("fused_pack"))
    assert hbm_traffic_bytes(fused) < hbm_traffic_bytes(sp)


# ---------------------------------------------------------------------------
# numerical parity: every registered variant vs the jnp oracle
# ---------------------------------------------------------------------------


TALL_SHAPES = [(256, 512, 8), (300, 520, 17)]        # aligned + ragged


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", TALL_SHAPES)
@pytest.mark.parametrize("spec", sampled_specs_for("tall_a"),
                         ids=lambda s: s.key())
def test_tall_variant_parity_interpret(spec, m, k, n, dtype):
    a, b = _mk((m, k), dtype), _mk((k, n), dtype)
    want = ref.tsmm_ref(a, b)
    got = run_tall_a(spec, a, b, bm=128, bk=128, packed=False,
                     impl="pallas_interpret")
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    # packed (block-major) input path
    ap = ops.pack_blocks(a, 128, 128)
    got_p = run_tall_a(spec, ap, b, packed=True, impl="pallas_interpret")[:m]
    np.testing.assert_allclose(np.asarray(got_p, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


SKINNY_SHAPES = [(4, 512, 256), (13, 640, 384)]      # aligned + ragged


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", SKINNY_SHAPES)
@pytest.mark.parametrize("spec", sampled_specs_for("skinny_a", prepack=False),
                         ids=lambda s: s.key())
def test_skinny_variant_parity_interpret(spec, m, k, n, dtype):
    x, w = _mk((m, k), dtype), _mk((k, n), dtype)
    bias = _mk((n,), dtype)
    want = ref.tsmm_ref(x, w, bias=bias, act="gelu")
    # natural-layout weight (per-call pack / pack-on-the-fly path)
    got = run_skinny_a(spec, x, w, bias, "gelu", bk=128, bn=128,
                       packed=False, impl="pallas_interpret")[:m, :n]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    # packed weight (serving path) — every variant must accept it
    wp = ops.pack_blocks(w, 128, 128)
    got_p = run_skinny_a(spec, x, wp, bias, "gelu", bk=128, bn=128,
                         packed=True, impl="pallas_interpret")[:m, :n]
    np.testing.assert_allclose(np.asarray(got_p, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("spec", sampled_specs_for("skinny_a", prepack=False),
                         ids=lambda s: s.key())
def test_skinny_variant_reads_layer_of_stacked_weight(spec, impl):
    """With ``layer``, every variant reads that row of a layer-stacked
    packed weight itself and gives what it gives on the row's slice."""
    m, k, n = SKINNY_SHAPES[0]
    x, bias = _mk((m, k), jnp.float32), _mk((n,), jnp.float32)
    stacked = jnp.stack([ops.pack_blocks(_mk((k, n), jnp.float32), 128, 128)
                         for _ in range(3)])
    for layer in range(3):
        got = run_skinny_a(spec, x, stacked, bias, "gelu", bk=128, bn=128,
                           packed=True, impl=impl,
                           layer=jnp.asarray(layer, jnp.int32))
        want = run_skinny_a(spec, x, stacked[layer], bias, "gelu", bk=128,
                            bn=128, packed=True, impl=impl)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_verify_variants_all_ok():
    rows = verify_variants(impl="xla")
    assert rows and all(r["ok"] for r in rows), rows
    specs = {(r["spec"], r["orientation"]) for r in rows}
    assert len(specs) == len(rows) >= 8


# ---------------------------------------------------------------------------
# k-split partial-sum property (hypothesis)
# ---------------------------------------------------------------------------


def _hyp():
    hypothesis = pytest.importorskip("hypothesis")
    return hypothesis, pytest.importorskip("hypothesis.strategies")


def test_ksplit_matches_unsplit_property():
    hypothesis, st = _hyp()

    @hypothesis.settings(max_examples=20, deadline=None)
    @hypothesis.given(st.integers(1, 16), st.sampled_from([256, 512, 1024]),
                      st.integers(1, 300), st.sampled_from([2, 4]))
    def prop(m, k, n, splits):
        rng = np.random.default_rng(m * k + n + splits)
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        spec = KernelSpec.make("ksplit", splits=splits)
        got = run_skinny_a(spec, x, w, bk=128, bn=128, packed=False,
                           impl="xla")[:m, :n]
        want = run_skinny_a(BASELINE, x, w, bk=128, bn=128, packed=False,
                            impl="xla")[:m, :n]
        # f32 partial sums reassociate the reduction: equal within
        # f32-accumulation tolerance, not bit-equal
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    prop()


# ---------------------------------------------------------------------------
# Plan round-trip + old-format registry back-compat
# ---------------------------------------------------------------------------


def test_plan_kernel_spec_json_roundtrip():
    plan = Plan(Problem(64, 4096, 512, "float32"), "skinny_a", bm=64,
                bk=512, bn=256, kernel=KernelSpec.make("ksplit", splits=4))
    back = Plan.from_json(json.loads(json.dumps(plan.to_json())))
    assert back == plan and back.kernel == plan.kernel


def test_old_format_registry_loads_as_baseline(cache_env, monkeypatch):
    """A checked-in PRE-VARIANT registry file (no "kernel" key anywhere)
    must load without KeyError and come back as baseline-variant plans."""
    path = cache_env / "plans.json"
    shutil.copy(DATA / "old_format_registry.json", path)
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(path))
    registry.clear_memory()
    skinny = registry.get("m64_k4096_n512_float32_s1")
    tall = registry.get("m8192_k4096_n16_float32_s1")
    assert skinny is not None and tall is not None
    assert skinny.kernel == BASELINE and tall.kernel == BASELINE
    assert skinny.chosen_by == "measured"
    # a baseline tuning key carries no variant suffix, so measurement
    # records cached before the variant axis existed keep matching
    assert "_kv:" not in skinny.tuning_key()


def test_measured_baseline_vs_variant_challenger(cache_env):
    """Provenance guard x variant axis: a measured baseline winner and a
    model-ranked variant challenger are DISTINCT tuning keys, and the
    challenger never displaces the measured winner."""
    prob = Problem(64, 4096, 512, "float32")
    measured = Plan(prob, "skinny_a", bm=64, bk=512, bn=256,
                    chosen_by="measured", score=1e-4)
    challenger = dataclasses.replace(
        measured, kernel=KernelSpec.make("ksplit", splits=2),
        chosen_by="model", score=5e-5)
    assert measured.tuning_key() != challenger.tuning_key()
    registry.put(measured, persist=False)
    stands = registry.put(challenger, persist=False)
    assert stands == measured
    # distinct measurement-cache slots: records for both can coexist
    r1 = registry.MeasureRecord(plan=measured, seconds=1e-4, iters=2,
                                dispersion=0.0)
    r2 = registry.MeasureRecord(plan=challenger, seconds=9e-5, iters=2,
                                dispersion=0.0)
    registry.record_measurement(r1)
    registry.record_measurement(r2)
    assert registry.lookup_measurement(measured).seconds == 1e-4
    assert registry.lookup_measurement(challenger).seconds == 9e-5


# ---------------------------------------------------------------------------
# REPRO_TSMM_VARIANT env override
# ---------------------------------------------------------------------------


def test_variant_choice_parses_and_validates(monkeypatch):
    from repro.core.tsmm import variant_choice
    monkeypatch.delenv("REPRO_TSMM_VARIANT", raising=False)
    assert variant_choice() is None
    monkeypatch.setenv("REPRO_TSMM_VARIANT", "ksplit:splits=4")
    assert variant_choice() == KernelSpec.make("ksplit", splits=4)
    monkeypatch.setenv("REPRO_TSMM_VARIANT", "not_a_kernel")
    with pytest.raises(ValueError) as exc:
        variant_choice()
    # the error lists every registered variant (debuggable typos)
    for name in variant_names():
        assert name in str(exc.value)


def test_env_override_forces_variant_dispatch(cache_env, monkeypatch):
    from repro.core.tsmm import tsmm_dot
    seen = []
    orig = variants.run_skinny_a

    def spy(spec, *a, **kw):
        seen.append(spec)
        return orig(spec, *a, **kw)

    monkeypatch.setattr(variants, "run_skinny_a", spy)
    monkeypatch.setenv("REPRO_TSMM_VARIANT", "epilogue_split")
    x, w = _mk((4, 512), jnp.float32), _mk((512, 256), jnp.float32)
    plan = Plan(Problem(4, 512, 256, "float32"), "skinny_a", bm=4,
                bk=128, bn=128, impl="xla")
    out = tsmm_dot(x, w, plan=plan, impl="xla")
    assert seen and seen[-1] == KernelSpec("epilogue_split")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.tsmm_ref(x, w), np.float32),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# evaluator / serving variant fidelity
# ---------------------------------------------------------------------------


VARIANT_PLANS = [
    Plan(Problem(4, 512, 256, "float32"), "skinny_a", bm=4, bk=128, bn=128,
         impl="xla", kernel=KernelSpec.make("ksplit", splits=2)),
    Plan(Problem(4, 512, 256, "float32"), "skinny_a", bm=4, bk=128, bn=128,
         impl="xla", kernel=KernelSpec("epilogue_split")),
    Plan(Problem(4, 512, 256, "float32"), "skinny_a", bm=4, bk=128, bn=128,
         impl="xla", prepack=False, kernel=KernelSpec("fused_pack")),
    Plan(Problem(1024, 512, 16, "float32"), "tall_a", bm=256, bk=128, bn=128,
         impl="xla", kernel=KernelSpec("kmajor")),
    Plan(Problem(1024, 512, 16, "float32"), "tall_a", bm=256, bk=128, bn=128,
         impl="xla", kernel=KernelSpec("b_resident")),
    Plan(Problem(1024, 512, 16, "float32"), "tall_a", bm=256, bk=128, bn=128,
         impl="xla", prepack=False,
         kernel=KernelSpec.make("ksplit", splits=2)),
]


@pytest.mark.parametrize("plan", VARIANT_PLANS,
                         ids=lambda p: f"{p.orientation}_{p.kernel.key()}"
                                       f"_pp{int(p.prepack)}")
def test_evaluator_times_what_serving_replays(plan):
    """parity_check: build_callable's output == tsmm_dot replaying the
    SAME variant plan — per registered variant."""
    evaluator.parity_check(plan)


def test_measure_plan_keys_variant_records(cache_env):
    plan = VARIANT_PLANS[0]
    rec = evaluator.measure_plan(plan, iters=2, warmup=1)
    assert rec.seconds > 0
    got = registry.lookup_measurement(plan)
    assert got is not None and got.plan.kernel == plan.kernel
    # the baseline sibling is a different slot
    assert registry.lookup_measurement(
        dataclasses.replace(plan, kernel=BASELINE)) is None


def test_packed_serving_replays_registry_variant(cache_env, monkeypatch):
    """The decode hot path: tsmm_dot on a PackedTensor must look up and
    execute whichever variant the registry recorded for the problem."""
    from repro.core.packing import pack
    from repro.core.tsmm import tsmm_dot
    prob = Problem(4, 512, 256, "float32")
    plan = predict(Plan(prob, "skinny_a", bm=4, bk=128, bn=128, impl="xla",
                        kernel=KernelSpec.make("ksplit", splits=2)))
    registry.put(dataclasses.replace(plan, chosen_by="measured"),
                 persist=False)
    seen = []
    orig = variants.run_skinny_a

    def spy(spec, *a, **kw):
        seen.append(spec)
        return orig(spec, *a, **kw)

    monkeypatch.setattr(variants, "run_skinny_a", spy)
    x, w = _mk((4, 512), jnp.float32), _mk((512, 256), jnp.float32)
    out = tsmm_dot(x, pack(w, 128, 128), impl="xla")
    assert seen and seen[-1] == KernelSpec.make("ksplit", splits=2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.tsmm_ref(x, w), np.float32),
                               rtol=2e-4, atol=2e-4)
    # stats untouched: the peek must not pollute engine miss telemetry
    assert registry.stats()["misses"] == 0


def test_override_only_applies_to_matching_orientation(cache_env,
                                                       monkeypatch):
    """Forcing a tall-only variant (kmajor) must not crash the skinny
    regime mid-inference — the override rebinds only its own regime."""
    from repro.core.tsmm import tsmm_dot
    monkeypatch.setenv("REPRO_TSMM_VARIANT", "kmajor")
    x, w = _mk((4, 512), jnp.float32), _mk((512, 256), jnp.float32)
    plan = Plan(Problem(4, 512, 256, "float32"), "skinny_a", bm=4,
                bk=128, bn=128, impl="xla")
    out = tsmm_dot(x, w, plan=plan, impl="xla")   # keeps the plan's kernel
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.tsmm_ref(x, w), np.float32),
                               rtol=2e-4, atol=2e-4)
    # and the tall regime DOES pick it up
    a, b = _mk((1024, 512), jnp.float32), _mk((512, 16), jnp.float32)
    tplan = Plan(Problem(1024, 512, 16, "float32"), "tall_a", bm=256,
                 bk=128, bn=128, impl="xla")
    seen = []
    orig = variants.run_tall_a

    def spy(spec, *args, **kw):
        seen.append(spec)
        return orig(spec, *args, **kw)

    monkeypatch.setattr(variants, "run_tall_a", spy)
    tsmm_dot(a, b, plan=tplan, impl="xla")
    assert seen and seen[-1] == KernelSpec("kmajor")


def test_prepacked_weight_replays_stamped_variant(cache_env, monkeypatch):
    """prepack_for stamps the tuned per-bucket variant on the
    PackedTensor; the decode path replays the stamp (this is what keeps
    sharded engines — whose registry keys use per-shard dims — on the
    recorded variant)."""
    from repro.core.tsmm import prepack_for, tsmm_dot
    prob = Problem(4, 512, 2048, "float32")
    winner = predict(Plan(prob, "skinny_a", bm=4, bk=128, bn=256,
                          impl="xla",
                          kernel=KernelSpec.make("ksplit", splits=2)))
    registry.put(dataclasses.replace(winner, chosen_by="measured"),
                 persist=False)
    w = _mk((512, 2048), jnp.float32)
    pk = prepack_for(4, w)
    assert pk is not None
    assert pk.kernel_specs == ((4, KernelSpec.make("ksplit", splits=2),
                                DEFAULT_SCHEDULE),)
    seen = []
    orig = variants.run_skinny_a

    def spy(spec, *args, **kw):
        seen.append(spec)
        return orig(spec, *args, **kw)

    monkeypatch.setattr(variants, "run_skinny_a", spy)
    x = _mk((4, 512), jnp.float32)
    out = tsmm_dot(x, pk, impl="xla")
    assert seen and seen[-1] == KernelSpec.make("ksplit", splits=2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.tsmm_ref(x, w), np.float32),
                               rtol=2e-4, atol=2e-4)


def test_stamp_regates_variant_at_packed_blocks(cache_env):
    """The stamp must only name variants valid at the blocks the tensor
    was ACTUALLY packed with — a tuned spec that does not transfer
    (fused_pack on a packed weight; ksplit whose splits no longer divide
    the k-block count) degrades to the baseline."""
    from repro.core.tsmm import _stamp_spec_for_blocks, prepack_for
    prob = Problem(4, 512, 2048, "float32")
    ksp4 = predict(Plan(prob, "skinny_a", bm=4, bk=128, bn=256, impl="xla",
                        kernel=KernelSpec.make("ksplit", splits=4)))
    # feasible at the tuned blocks (nk=4)...
    assert _stamp_spec_for_blocks(ksp4, 128, 256) == (ksp4.kernel,
                                                      DEFAULT_SCHEDULE)
    # ...but not at bk=512 (nk=1, 4 does not divide it)
    assert _stamp_spec_for_blocks(ksp4, 512, 256) == (BASELINE,
                                                      DEFAULT_SCHEDULE)
    # a fused_pack (prepack=False-only) winner cannot replay on a packed
    # weight: prepack_for stamps the baseline, matching what serves
    fused = predict(Plan(prob, "skinny_a", bm=4, bk=128, bn=256,
                         impl="xla", prepack=False,
                         kernel=KernelSpec("fused_pack")))
    registry.put(dataclasses.replace(fused, chosen_by="measured"),
                 persist=False)
    pk = prepack_for(4, _mk((512, 2048), jnp.float32))
    assert pk is not None and pk.kernel_specs == ((4, BASELINE,
                                                   DEFAULT_SCHEDULE),)


def test_fused_pack_on_packed_weight_falls_back(cache_env):
    """A fused_pack spec against an already-packed weight has no pack to
    fuse: the variant serves the baseline kernel instead of failing."""
    from repro.core.packing import pack
    x, w = _mk((4, 512), jnp.float32), _mk((512, 256), jnp.float32)
    wp = pack(w, 128, 128)
    out = run_skinny_a(KernelSpec("fused_pack"), x, wp.blocks,
                       packed=True, impl="xla")[:, :256]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.tsmm_ref(x, w), np.float32),
                               rtol=2e-4, atol=2e-4)


def test_parse_spec_rejects_unknown():
    with pytest.raises(ValueError, match="registered variants"):
        parse_spec("warp_speed")
