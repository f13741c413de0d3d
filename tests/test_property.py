"""Hypothesis property tests on system invariants."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")
from hypothesis import given, settings

from repro.core import registry
from repro.core.autotuner import candidate_blocks, make_plan
from repro.core.hw import VMEM_LIMIT_BYTES
from repro.core.plan import BucketGrid, Problem, is_tsmm
from repro.core.vmem_model import feasible, vmem_bytes_needed
from repro.kernels import ops, ref
from repro.sharding.rules import SKINNY_MIN_PER_SHARD, pspec_for, ShardingOptions

SET = settings(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# plan invariants (the paper's Eq.2/3 as hard properties)
# ---------------------------------------------------------------------------

problem_st = st.builds(
    Problem,
    m=st.integers(1, 1 << 18).map(lambda x: max(x, 1)),
    k=st.sampled_from([512, 768, 1024, 4096, 16384, 25600]),
    n=st.integers(1, 512),
    dtype=st.sampled_from(["float32", "bfloat16"]),
)


@SET
@given(problem_st)
def test_candidates_respect_vmem_bound(problem):
    for plan in candidate_blocks(problem):
        assert feasible(plan)
        assert vmem_bytes_needed(plan) <= VMEM_LIMIT_BYTES
        # MXU alignment (the register-blocking analogue)
        assert plan.bk % 128 == 0 and plan.bn % 128 == 0
        # grid covers the problem
        if plan.orientation == "tall_a":
            assert plan.grid[0] * plan.bm >= problem.m
        else:
            assert plan.grid[0] * plan.bn >= problem.n
        assert plan.grid[1] * plan.bk >= problem.k


@SET
@given(problem_st)
def test_plan_deterministic_and_cached(problem):
    registry.clear_memory()
    p1 = make_plan(problem, persist=False)
    p2 = make_plan(problem, persist=False)   # cache hit
    assert p1 == p2


@SET
@given(st.integers(1, 4096), st.integers(128, 32768), st.integers(1, 4096))
def test_is_tsmm_symmetry(m, k, n):
    # the skinny test must not care which operand is skinny
    assert is_tsmm(m, k, n) == is_tsmm(n, k, m)


# ---------------------------------------------------------------------------
# the skinny no-shard rule
# ---------------------------------------------------------------------------


@SET
@given(st.integers(1, 2048), st.integers(1, 2048))
def test_no_shard_skinny_rule(rows, cols):
    import jax
    mesh = jax.sharding.Mesh(
        np.array(jax.devices() * 16)[:16].reshape(4, 4), ("data", "model"))
    spec = pspec_for(("embed", "mlp"), (rows, cols), mesh,
                     ShardingOptions(fsdp=True))
    for dim, ax in zip((rows, cols), spec):
        if ax is not None:
            n = mesh.shape[ax] if isinstance(ax, str) else \
                int(np.prod([mesh.shape[a] for a in ax]))
            assert dim % n == 0
            assert dim // n >= SKINNY_MIN_PER_SHARD


# ---------------------------------------------------------------------------
# kernel math properties
# ---------------------------------------------------------------------------


@SET
@given(st.integers(1, 7), st.integers(1, 6), st.integers(1, 40),
       st.integers(0, 3))
def test_pack_roundtrip_property(bm8, bk128, mfrac, extra):
    bm, bk = bm8 * 8, bk128 * 128
    m = max(1, (bm * mfrac) // 3 + extra)
    k = bk * 2 + extra * 7
    a = jnp.asarray(np.random.default_rng(m * k).standard_normal((m, k)),
                    jnp.float32)
    ap = ops.pack_blocks(a, bm, bk)
    nm, nk, pbm, pbk = ap.shape
    assert pbm == bm and pbk == bk
    assert nm * bm >= m and (nm - 1) * bm < m
    back = ops.unpack_blocks(ap, m, k)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(a))


@SET
@given(st.integers(1, 64), st.sampled_from([256, 384, 512]),
       st.integers(1, 300))
def test_tsmm_matches_ref_property(m, k, n):
    rng = np.random.default_rng(m + k + n)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    wp = ops.pack_blocks(w, 128, 128)
    got = ops.tsmm_skinny(x, wp, impl="xla")[:, :n]
    want = ref.tsmm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the 2D bucket grid (ragged admission, DESIGN.md §8)
# ---------------------------------------------------------------------------


grid_st = st.builds(BucketGrid.build, st.integers(1, 256),
                    st.integers(1, 4096))


@SET
@given(grid_st, st.integers(1, 256), st.integers(1, 4096))
def test_grid_admission_minimal_and_waste_bounded(grid, b, s):
    if b > grid.max_batch or s > grid.max_prompt:
        with pytest.raises(ValueError):
            grid.cell_for(b, s)
        return
    bb, lb = grid.cell_for(b, s)
    # covering
    assert bb >= b and lb >= s
    assert bb in grid.batch and lb in grid.length
    # minimal: no smaller bucket on either axis covers the request
    assert all(x < b for x in grid.batch if x < bb)
    assert all(x < s for x in grid.length if x < lb)
    # power-of-two ladders bound the waste: each axis pads < 2x except at
    # its floor bucket
    assert bb < 2 * b or bb == grid.batch[0]
    assert lb < 2 * s or lb == grid.length[0]
    waste = grid.padding_waste(b, s)
    assert 0 <= waste == bb * lb - b * s
    assert bb * lb <= max(4 * b * s, 2 * b * grid.length[0],
                          2 * s * grid.batch[0], grid.batch[0] * grid.length[0])


@SET
@given(st.integers(1, 64), st.integers(1, 64))
def test_grid_cells_cover_every_admissible_request(mb, mp):
    grid = BucketGrid.build(mb, mp)
    cells = set(grid.cells())
    for b in range(1, mb + 1):
        for s in range(1, mp + 1):
            assert grid.cell_for(b, s) in cells
    assert grid.token_buckets()[-1] == grid.max_batch * grid.max_prompt


@functools.lru_cache(maxsize=1)
def _ragged_engine():
    from repro.configs import get_reduced_config
    from repro.models.registry import build_model
    from repro.serve.engine import Engine
    cfg = get_reduced_config("qwen1_5_4b").reduced(
        d_model=256, d_ff=512, num_layers=2, vocab_size=512,
        num_heads=4, num_kv_heads=4, head_dim=64, dtype="float32")
    model = build_model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    return cfg, Engine(model, params, axes, max_len=64, max_batch=4,
                       prepack=False)


@settings(max_examples=8, deadline=None)
@given(st.integers(2, 4), st.integers(2, 24), st.integers(1, 3))
def test_ragged_decode_matches_unpadded_reference(b, s, steps):
    """End-to-end grid property: a RAGGED group (mixed prompt lengths,
    left-padded to its length bucket with per-row masking) decodes the
    SAME tokens as each request's unpadded solo reference (f32 model so
    RoPE-shift float noise cannot flip an argmax)."""
    cfg, eng = _ragged_engine()
    rng = np.random.default_rng(b * 1000 + s * 10 + steps)
    lens = [s if i % 2 == 0 else max(1, s // 2) for i in range(b)]
    reqs = [{"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=n), jnp.int32)} for n in lens]
    outs = eng.serve(reqs, steps=steps)
    for r, o in zip(reqs, outs):
        ref = eng.generate({"tokens": r["tokens"][None]}, steps=steps)
        np.testing.assert_array_equal(np.asarray(o.tokens),
                                      np.asarray(ref.tokens))


# ---------------------------------------------------------------------------
# data pipeline determinism
# ---------------------------------------------------------------------------


@SET
@given(st.integers(0, 1 << 20), st.integers(0, 1 << 20))
def test_data_deterministic_and_step_dependent(step_a, step_b):
    from repro.data.pipeline import synth_tokens
    ta = synth_tokens(1, step_a, np.arange(4), 16, 1000)
    ta2 = synth_tokens(1, step_a, np.arange(4), 16, 1000)
    np.testing.assert_array_equal(ta, ta2)
    assert ta.min() >= 0 and ta.max() < 1000
    if step_a != step_b:
        tb = synth_tokens(1, step_b, np.arange(4), 16, 1000)
        assert not np.array_equal(ta, tb)


# ---------------------------------------------------------------------------
# optimizer invariants
# ---------------------------------------------------------------------------


@SET
@given(st.sampled_from(["float32", "bfloat16"]),
       st.sampled_from([None, "bf16", "bf16_ef"]))
def test_adamw_moves_params_and_keeps_dtypes(moment_dtype, compress):
    from repro.optim.adamw import OptConfig, apply_updates, init_opt_state
    ocfg = OptConfig(moment_dtype=moment_dtype, compress=compress,
                     warmup_steps=0)
    params = {"w": jnp.ones((8, 8), jnp.float32)}
    state = init_opt_state(ocfg, params)
    grads = {"w": jnp.full((8, 8), 0.5, jnp.float32)}
    new_p, new_s, stats = apply_updates(ocfg, params, grads, state)
    assert new_p["w"].dtype == jnp.float32
    assert new_s["m"]["w"].dtype == jnp.dtype(moment_dtype)
    assert float(jnp.abs(new_p["w"] - params["w"]).max()) > 0
    assert np.isfinite(float(stats["grad_norm"]))
