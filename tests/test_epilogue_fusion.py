"""Grid schedules + fused tall-A epilogues (DESIGN.md §11): fused-vs-
post-hoc numerical parity for every tall variant x dtype x {bias} x
{act}, ScheduleSpec round-trip/tuning-key back-compat, the feasibility
gates as a hypothesis property, the REPRO_TSMM_SCHEDULE override, the
provenance guard against scheduled model plans, evaluator/serving
schedule fidelity, and the measurement-cache cap."""

import dataclasses
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import evaluator, registry
from repro.core.autotuner import candidate_blocks
from repro.core.hw import TPU_V5E
from repro.core.plan import (DEFAULT_SCHEDULE, FIXED_SCHEDULE_KERNELS,
                             M_SPLIT_KERNELS, Plan, Problem, ScheduleSpec,
                             parse_schedule)
from repro.core.registry import MeasureRecord
from repro.core.vmem_model import (epilogue_roundtrip_bytes, feasible,
                                   hbm_traffic_bytes, overhead_steps,
                                   vmem_bytes_needed)
from repro.kernels import ref
from repro.kernels.variants import (KernelSpec, run_tall_a,
                                    sampled_specs_for, specs_for)

RNG = np.random.default_rng(11)


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
    return dict(rtol=2e-2, atol=3e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# fused epilogue parity: every tall variant x {bias} x {act} x dtype
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("spec", sampled_specs_for("tall_a"),
                         ids=lambda s: s.key())
def test_tall_fused_epilogue_matches_posthoc(spec, dtype):
    """act(A@B + bias) fused into the variant's epilogue must equal the
    pre-fusion behavior (matmul kernel + separate bias/act pass) for
    every tall variant, with and without bias, for every activation —
    interpret mode, so the actual Pallas kernel bodies are exercised."""
    a, b = _mk((64, 256), dtype), _mk((256, 8), dtype)
    bias_full = _mk((8,), dtype)
    for bias in (bias_full, None):
        for act in ("gelu", "silu", None):
            fused = run_tall_a(spec, a, b, bias, act, bm=16, bk=128,
                               packed=False, impl="pallas_interpret")
            post = run_tall_a(spec, a, b, bm=16, bk=128, packed=False,
                              impl="pallas_interpret")
            if bias is not None:
                post = post + bias.astype(post.dtype)
            post = ref.act_ref(post.astype(jnp.float32), act
                               ).astype(post.dtype)
            np.testing.assert_allclose(
                np.asarray(fused, np.float32), np.asarray(post, np.float32),
                err_msg=f"spec={spec.key()} bias={bias is not None} "
                        f"act={act}", **_tol(dtype))


def test_fused_epilogue_matches_oracle_packed():
    """Packed tall-A path (pre-packed A blocks) fuses too."""
    from repro.kernels import ops
    a, b = _mk((64, 256), jnp.float32), _mk((256, 8), jnp.float32)
    bias = _mk((8,), jnp.float32)
    ap = ops.pack_blocks(a, 16, 128)
    for spec in sampled_specs_for("tall_a"):
        got = run_tall_a(spec, ap, b, bias, "silu", bm=16, bk=128,
                         packed=True, impl="pallas_interpret")[:64, :8]
        want = ref.tsmm_ref(a, b, bias=bias, act="silu")
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   err_msg=spec.key(), **_tol(jnp.float32))


def test_tsmm_dot_tall_plan_has_no_posthoc_pass(cache_env, monkeypatch):
    """The planned tall-A path must route bias/act INTO run_tall_a (the
    fused kernel), not apply them afterwards."""
    from repro.core import tsmm as core_tsmm
    seen = {}
    orig = core_tsmm.variants.run_tall_a

    def spy(spec, a, b, bias=None, act=None, **kw):
        seen["bias"], seen["act"] = bias is not None, act
        return orig(spec, a, b, bias, act, **kw)

    monkeypatch.setattr(core_tsmm.variants, "run_tall_a", spy)
    prob = Problem(2048, 512, 16, "float32")
    plan = candidate_blocks(prob)[0]
    a, b = _mk((2048, 512), jnp.float32), _mk((512, 16), jnp.float32)
    bias = _mk((16,), jnp.float32)
    out = core_tsmm.tsmm_dot(a, b, bias=bias, act="gelu", plan=plan,
                             impl="xla")
    assert seen == {"bias": True, "act": "gelu"}
    want = ref.tsmm_ref(a, b, bias=bias, act="gelu")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **_tol(jnp.float32))


def test_linear_routes_tsmm_shaped_matmul_in_serving_ctx(
        cache_env, monkeypatch):
    """core.linear sends TSMM-shaped unpacked matmuls (the prefill gate
    projections) through the planned fused path — but ONLY inside the
    engine's serving context: the Pallas kernels carry no AD rule, so a
    training trace must keep the plain differentiable GEMM."""
    from repro.core import linear as linear_mod
    calls = []
    orig = linear_mod.tsmm_dot
    monkeypatch.setattr(linear_mod, "tsmm_dot",
                        lambda *a, **kw: calls.append(1) or orig(*a, **kw))
    x = _mk((4, 512, 512), jnp.float32)        # (batch, seq, d): m = 2048
    w = _mk((512, 16), jnp.float32)
    bias = _mk((16,), jnp.float32)
    want = ref.tsmm_ref(np.asarray(x).reshape(2048, 512), w, bias=bias,
                        act="silu")
    # outside serving (training path): plain GEMM, no planned dispatch
    got = linear_mod.linear(x, w, bias, act="silu")
    assert not calls
    with linear_mod.serving_ctx():
        got = linear_mod.linear(x, w, bias, act="silu")
    assert calls and got.shape == (4, 512, 16)
    np.testing.assert_allclose(
        np.asarray(got, np.float32).reshape(2048, 16),
        np.asarray(want, np.float32), **_tol(jnp.float32))


# ---------------------------------------------------------------------------
# ScheduleSpec: round-trip, tuning keys, back-compat
# ---------------------------------------------------------------------------


def test_schedule_spec_json_round_trip():
    for s in (ScheduleSpec(), ScheduleSpec(m_split=2),
              ScheduleSpec(multibuffer=3, dims=("parallel", "arbitrary")),
              ScheduleSpec(m_split=4, multibuffer=3)):
        assert ScheduleSpec.from_json(s.to_json()) == s
    assert ScheduleSpec.from_json(None) == DEFAULT_SCHEDULE


def test_parse_schedule():
    s = parse_schedule("m_split=2,multibuffer=3,dims=parallel;arbitrary")
    assert s == ScheduleSpec(dims=("parallel", "arbitrary"), m_split=2,
                             multibuffer=3)
    assert parse_schedule("") == DEFAULT_SCHEDULE
    with pytest.raises(ValueError, match="unknown schedule field"):
        parse_schedule("warp=9")
    with pytest.raises(ValueError, match="semantics"):
        parse_schedule("dims=sideways")


def test_default_schedule_keeps_tuning_key():
    """Pre-schedule measurement records must keep matching: a default
    schedule adds NO tuning-key suffix; a non-default one does."""
    prob = Problem(2048, 2048, 128, "float32")
    base = Plan(prob, "tall_a", bm=512, bk=512, bn=128)
    assert "_sch:" not in base.tuning_key()
    sched = dataclasses.replace(base, schedule=ScheduleSpec(m_split=2))
    assert sched.tuning_key() == base.tuning_key() + "_sch:ms2"


def test_plan_json_round_trip_and_old_format():
    prob = Problem(2048, 2048, 128, "float32")
    plan = Plan(prob, "tall_a", bm=512, bk=512, bn=128,
                schedule=ScheduleSpec(m_split=2, multibuffer=3))
    assert Plan.from_json(plan.to_json()) == plan
    # a pre-schedule record (no "schedule" key) decodes to the default
    d = plan.to_json()
    del d["schedule"]
    assert Plan.from_json(d).schedule == DEFAULT_SCHEDULE


def test_old_format_registry_file_loads(cache_env, tmp_path):
    """The PR-4-era fixture (no kernel, no schedule fields) must still
    load, decoding to baseline kernel + default schedule."""
    import shutil
    from pathlib import Path
    fixture = Path(__file__).parent / "data" / "old_format_registry.json"
    path = cache_env / "plans.json"
    shutil.copy(fixture, path)
    registry.clear_memory()
    plan = registry.get("m8192_k4096_n16_float32_s1")
    assert plan is not None
    assert plan.schedule == DEFAULT_SCHEDULE and plan.kernel.is_baseline


# ---------------------------------------------------------------------------
# feasibility gates (+ hypothesis property)
# ---------------------------------------------------------------------------


def test_schedule_feasibility_gates():
    prob = Problem(4096, 2048, 128, "float32")
    base = Plan(prob, "tall_a", bm=512, bk=512, bn=128)     # 8 row panels
    assert feasible(base)
    ok = dataclasses.replace(base, schedule=ScheduleSpec(m_split=4))
    assert feasible(ok)
    # m_split must divide the row-panel count
    bad = dataclasses.replace(base, schedule=ScheduleSpec(m_split=3))
    assert not feasible(bad)
    # fixed-schedule kernels admit only the default schedule
    km = dataclasses.replace(base, kernel=KernelSpec("kmajor"),
                             schedule=ScheduleSpec(multibuffer=3))
    assert not feasible(km)
    # M partitioning is a tall-A notion
    sk = Plan(prob, "skinny_a", bm=prob.m, bk=512, bn=128,
              schedule=ScheduleSpec(m_split=2))
    assert not feasible(sk)
    # deeper buffering costs VMEM: footprint strictly grows with depth
    mb3 = dataclasses.replace(base, schedule=ScheduleSpec(multibuffer=3))
    assert vmem_bytes_needed(mb3) > vmem_bytes_needed(base)
    # bad dims rank / names are rejected
    assert not feasible(dataclasses.replace(
        base, schedule=ScheduleSpec(dims=("parallel",))))
    assert not feasible(dataclasses.replace(
        base, schedule=ScheduleSpec(dims=("parallel", "sideways"))))


def test_schedule_hypothesis_feasibility_property():
    """Property: the gates never admit an infeasible scheduled plan —
    anything ``feasible`` accepts has a divisible M partition, a VMEM
    footprint under budget, and a supporting kernel."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    from repro.core.hw import VMEM_LIMIT_BYTES

    kernels = st.sampled_from(
        [KernelSpec(), KernelSpec.make("ksplit", splits=2),
         KernelSpec("kmajor"), KernelSpec("b_resident")])

    @settings(max_examples=200, deadline=None)
    @given(
        m=st.sampled_from([2048, 4096, 8192]),
        k=st.sampled_from([512, 2048, 4096]),
        n=st.sampled_from([16, 128, 256]),
        bm=st.sampled_from([128, 256, 512, 1024]),
        bk=st.sampled_from([128, 512, 2048]),
        kernel=kernels,
        m_split=st.integers(min_value=0, max_value=6),
        multibuffer=st.integers(min_value=0, max_value=6),
    )
    def check(m, k, n, bm, bk, kernel, m_split, multibuffer):
        sched = ScheduleSpec(m_split=m_split, multibuffer=multibuffer)
        plan = Plan(Problem(m, k, n, "float32"), "tall_a", bm=bm, bk=bk,
                    bn=n, kernel=kernel, schedule=sched)
        if not feasible(plan, TPU_V5E):
            return
        # every gate must actually hold for an admitted plan
        assert 2 <= multibuffer <= 4 and m_split >= 1
        assert vmem_bytes_needed(plan, TPU_V5E) <= VMEM_LIMIT_BYTES
        if m_split > 1:
            assert kernel.name in M_SPLIT_KERNELS
            assert plan.grid[0] % m_split == 0
        if not sched.is_default:
            assert kernel.name not in FIXED_SCHEDULE_KERNELS
        assert overhead_steps(plan) > 0

    check()


def test_candidate_blocks_crosses_schedules_feasibly(cache_env):
    """The autotuner's schedule axis: non-default schedules appear among
    the candidates, every candidate is feasible, and default-schedule
    candidates exist for every surviving kernel variant."""
    cands = candidate_blocks(Problem(4096, 2048, 128, "float32"))
    assert cands and all(feasible(c) for c in cands)
    keys = {c.schedule.key() for c in cands}
    assert "default" in keys and len(keys) > 1
    assert any(c.schedule.m_split > 1 for c in cands)
    for c in cands:
        if c.schedule.m_split > 1:
            assert c.grid[0] % c.schedule.m_split == 0
            assert c.kernel.name in M_SPLIT_KERNELS


# ---------------------------------------------------------------------------
# cost model: fusion credit + schedule terms
# ---------------------------------------------------------------------------


def test_hbm_traffic_fusion_credit():
    """A fused plan's traffic must be exactly one (m, n) read+write below
    the post-hoc accounting — the acceptance criterion's model credit."""
    prob = Problem(4096, 2048, 128, "float32")
    for kernel in (KernelSpec(), KernelSpec("b_resident")):
        plan = Plan(prob, "tall_a", bm=512, bk=512, bn=128, kernel=kernel)
        credit = epilogue_roundtrip_bytes(plan)
        assert credit == 2 * 4096 * 128 * 4
        assert (hbm_traffic_bytes(plan, epilogue="posthoc")
                - hbm_traffic_bytes(plan)) == credit


def test_overhead_steps_schedule_terms():
    prob = Problem(4096, 2048, 128, "float32")
    base = Plan(prob, "tall_a", bm=512, bk=512, bn=128)
    assert overhead_steps(base) == float(base.grid[1])
    mb3 = dataclasses.replace(base, schedule=ScheduleSpec(multibuffer=3))
    assert overhead_steps(mb3) == pytest.approx(base.grid[1] * 2 / 3)
    ms4 = dataclasses.replace(base, schedule=ScheduleSpec(m_split=4))
    assert overhead_steps(ms4) == float(base.grid[1] + 3)


# ---------------------------------------------------------------------------
# provenance guard + env override + evaluator fidelity
# ---------------------------------------------------------------------------


def test_measured_preschedule_winner_survives_scheduled_model_plan(
        cache_env):
    """Acceptance criterion: a measured pre-schedule winner is never
    displaced by a model-ranked scheduled plan."""
    prob = Problem(4096, 2048, 128, "float32")
    measured = Plan(prob, "tall_a", bm=512, bk=512, bn=128,
                    chosen_by="measured", score=1e-4)
    registry.put(measured, persist=False)
    challenger = Plan(prob, "tall_a", bm=1024, bk=512, bn=128,
                      schedule=ScheduleSpec(m_split=2, multibuffer=3),
                      chosen_by="model", score=1e-9)
    stood = registry.put(challenger, persist=False)
    assert stood == measured
    assert registry.get(prob.key()).schedule == DEFAULT_SCHEDULE


def test_schedule_env_override(cache_env, monkeypatch):
    from repro.core import tsmm as core_tsmm
    prob = Problem(2048, 512, 16, "float32")
    plan = next(c for c in candidate_blocks(prob)
                if c.kernel.is_baseline and c.schedule.is_default
                and c.grid[0] % 2 == 0)
    seen = {}
    orig = core_tsmm.variants.run_tall_a

    def spy(spec, a, b, bias=None, act=None, **kw):
        seen["schedule"] = kw.get("schedule")
        return orig(spec, a, b, bias, act, **kw)

    monkeypatch.setattr(core_tsmm.variants, "run_tall_a", spy)
    a, b = _mk((2048, 512), jnp.float32), _mk((512, 16), jnp.float32)
    monkeypatch.setenv("REPRO_TSMM_SCHEDULE", "m_split=2")
    out = core_tsmm.tsmm_dot(a, b, plan=plan, impl="xla")
    assert seen["schedule"] == ScheduleSpec(m_split=2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.tsmm_ref(a, b), np.float32),
                               **_tol(jnp.float32))
    monkeypatch.setenv("REPRO_TSMM_SCHEDULE", "bogus=1")
    with pytest.raises(ValueError, match="unknown schedule field"):
        core_tsmm.tsmm_dot(a, b, plan=plan, impl="xla")


def test_evaluator_times_scheduled_plan_with_parity(cache_env):
    """build_callable must replay the plan's schedule and stay in parity
    with the tsmm_dot serving path (the stopwatch times what serves)."""
    prob = Problem(4096, 2048, 128, "float32")
    plan = next(c for c in candidate_blocks(prob)
                if c.kernel.is_baseline
                and c.schedule == ScheduleSpec(m_split=2))
    evaluator.parity_check(plan, impl="xla")
    rec = evaluator.measure_plan(plan, impl="xla", warmup=0, iters=1)
    assert "_sch:ms2" in rec.plan.tuning_key()
    assert registry.lookup_measurement(plan) is not None


# ---------------------------------------------------------------------------
# measurement-cache cap (satellite)
# ---------------------------------------------------------------------------


def _fake_record(plan, t_wall):
    return MeasureRecord(plan=plan, seconds=1e-3, iters=1, dispersion=0.0,
                         impl="xla", source="test", wall_time=t_wall)


def test_measurement_cache_cap_evicts_stale_oldest_first(cache_env):
    """Over the cap, records whose tuning keys candidate_blocks no longer
    produces are evicted oldest-first; live records always survive."""
    reg = registry.default()
    prob = Problem(4096, 2048, 128, "float32")
    live = candidate_blocks(prob)[:4]
    for i, plan in enumerate(live):
        reg.record_measurement(_fake_record(plan, t_wall=1000.0 + i))
    # stale: block shapes the ladders never produce (bn=384 not a
    # candidate; bk=384 not 128*2^j) — distinct tuning keys per record
    stale = [Plan(prob, "tall_a", bm=384, bk=384, bn=384,
                  impl=f"fake{i}") for i in range(4)]
    for i, plan in enumerate(stale):
        reg.record_measurement(_fake_record(plan, t_wall=float(i)))
    assert len(reg.measurements()) == 8
    dropped = reg.prune_measurements(cap=6)
    assert dropped == 2
    left = {r.plan.tuning_key() for r in reg.measurements()}
    # the two OLDEST stale records went; all live ones stayed
    assert stale[0].tuning_key() not in left
    assert stale[1].tuning_key() not in left
    assert {p.tuning_key() for p in live} <= left
    # under the cap nothing is evicted, even stale records
    assert reg.prune_measurements(cap=6) == 0
    # live records are never evicted, even over the cap
    assert reg.prune_measurements(cap=1) == 2
    assert {p.tuning_key() for p in reg.measurements() for p in [p.plan]} \
        == {p.tuning_key() for p in live}


def test_measure_record_wall_time_round_trip(cache_env):
    prob = Problem(4096, 2048, 128, "float32")
    plan = candidate_blocks(prob)[0]
    rec = _fake_record(plan, t_wall=time.time())
    decoded = MeasureRecord.from_json(json.loads(json.dumps(rec.to_json())))
    assert decoded.wall_time == rec.wall_time
    # pre-cap records (no wall_time in JSON) decode as oldest
    d = rec.to_json()
    del d["wall_time"]
    assert MeasureRecord.from_json(d).wall_time == 0.0
