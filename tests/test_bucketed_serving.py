"""Batch-adaptive serving runtime (DESIGN.md §7): bucket helpers, PlanSet,
multi-bucket pre-pack conformance, the Engine's admission layer, and the
install-then-lookup-only contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serve.engine as engine_mod
from repro.core import registry
from repro.core.autotuner import make_plan_set
from repro.core.plan import PlanSet, bucket_for, buckets_for
from repro.core.tsmm import prepack_for, tsmm_dot
from repro.serve.engine import Engine


def test_bucket_helpers():
    assert buckets_for(64) == (1, 2, 4, 8, 16, 32, 64)
    assert buckets_for(1) == (1,)
    assert buckets_for(6) == (1, 2, 4, 6)      # max_batch always a bucket
    assert bucket_for(3, buckets_for(64)) == 4
    assert bucket_for(64, buckets_for(64)) == 64
    with pytest.raises(ValueError):
        bucket_for(65, buckets_for(64))


def test_plan_set_fill_dispatch_roundtrip():
    buckets = buckets_for(32)
    pset = make_plan_set(4096, 128, buckets, "bfloat16", persist=False)
    assert pset.buckets  # (m, 4096, 128) is TSMM for every small bucket
    for m in (1, 3, 9):
        plan = pset.for_batch(m)
        assert plan.problem.m == bucket_for(m, pset.buckets)
    # above all buckets -> None: a smaller bucket's plan has bm = its own
    # problem.m and would be mistuned; the caller splits or uses plain GEMM
    assert pset.for_batch(1000) is None
    assert PlanSet({}).for_batch(1) is None
    back = PlanSet.from_json(pset.to_json())
    assert back == pset


def test_prepack_multibucket_blocks_conform():
    buckets = (1, 2, 4, 8)
    w = jax.random.normal(jax.random.PRNGKey(0), (512, 1024),
                          jnp.float32).astype(jnp.bfloat16)
    pk = prepack_for(buckets, w)
    assert pk is not None
    bk, bn = pk.block_shape
    assert 512 % bk == 0 and 1024 % bn == 0 and bk % 128 == 0 and bn % 128 == 0
    for m in (1, 3, 8):          # ONE packed layout serves every bucket
        x = jax.random.normal(jax.random.PRNGKey(m), (m, 512),
                              jnp.float32).astype(jnp.bfloat16)
        got = np.asarray(tsmm_dot(x, pk), np.float32)
        want = np.asarray(x @ w, np.float32)
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-1)


@pytest.fixture(scope="module")
def small_model():
    from repro.configs import get_reduced_config
    from repro.models.registry import build_model
    cfg = get_reduced_config("qwen1_5_4b").reduced(
        d_model=512, d_ff=1024, num_layers=2, vocab_size=1024,
        num_heads=8, num_kv_heads=8, head_dim=64)
    model = build_model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    return model, params, axes


def _group(cfg, b, s=12):
    return {"tokens": (jnp.arange(b * s).reshape(b, s)
                       % cfg.vocab_size).astype(jnp.int32)}


def test_engine_variable_batches_single_pack(small_model, monkeypatch):
    """The acceptance scenario: a request stream with varying batch sizes
    is served from the correct buckets off ONE packed param tree — no
    re-pack between batches — and each bucket's packed logits match the
    unpacked path."""
    model, params, axes = small_model
    calls = {"n": 0}
    real = engine_mod.pack_tree_for_serving

    def counted(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(engine_mod, "pack_tree_for_serving", counted)
    eng = Engine(model, params, axes, max_len=48, max_batch=8, prepack=True)
    assert calls["n"] == 1
    assert eng.buckets == (1, 2, 4, 8)
    assert len(eng.pack_report) >= 4

    # any further packing attempt while serving is a bug
    def boom(*a, **kw):
        raise AssertionError("re-pack during serving")
    monkeypatch.setattr(engine_mod, "prepack_for", boom)
    monkeypatch.setattr(engine_mod, "pack_tree_for_serving", boom)

    for b, want_bucket in ((3, 4), (8, 8), (1, 1)):
        res = eng.generate(_group(model.cfg, b), steps=2)
        assert res.buckets == (want_bucket,)
        assert res.tokens.shape == (b, 2)
        assert bool(jnp.isfinite(res.logits_last.astype(jnp.float32)).all())

    # oversize groups split into max_batch chunks
    res = eng.generate(_group(model.cfg, 11), steps=2)
    assert res.tokens.shape == (11, 2)
    assert res.buckets == (8, 4)

    # per-bucket parity with the unpacked path (same packed tree for all)
    for bucket in (1, 4, 8):
        batch = _group(model.cfg, bucket)
        cache = model.init_cache(bucket, 48)
        l_packed, c_p = model.prefill(eng.params, batch, cache)
        l_dense, c_d = model.prefill(params, batch, cache)
        np.testing.assert_allclose(np.asarray(l_packed, np.float32),
                                   np.asarray(l_dense, np.float32),
                                   rtol=5e-2, atol=5e-1)
        t = jnp.zeros((bucket, 1), jnp.int32)
        s_packed, _ = model.decode_step(eng.params, c_p, t)
        s_dense, _ = model.decode_step(params, c_d, t)
        np.testing.assert_allclose(np.asarray(s_packed, np.float32),
                                   np.asarray(s_dense, np.float32),
                                   rtol=5e-2, atol=5e-1)


def test_padding_rows_do_not_change_live_rows(small_model):
    # dense arch: padding must be bit-invariant.  (MoE archs are only
    # deterministic per bucket — capacity scales with the padded token
    # count; see DESIGN.md §7.)
    model, params, axes = small_model
    eng = Engine(model, params, axes, max_len=48, max_batch=4, prepack=True)
    g3 = _group(model.cfg, 3)
    g4 = {"tokens": jnp.concatenate(
        [g3["tokens"], jnp.zeros((1, 12), jnp.int32)])}
    r3, r4 = eng.generate(g3, 3), eng.generate(g4, 3)
    np.testing.assert_array_equal(np.asarray(r3.tokens),
                                  np.asarray(r4.tokens[:3]))
    np.testing.assert_allclose(np.asarray(r3.logits_last, np.float32),
                               np.asarray(r4.logits_last[:3], np.float32),
                               atol=1e-6)


def test_serve_admission_layer(small_model):
    model, params, axes = small_model
    eng = Engine(model, params, axes, max_len=48, max_batch=4, prepack=False)
    reqs = [{"tokens": (jnp.arange(12) * (i + 1)
                        % model.cfg.vocab_size).astype(jnp.int32)}
            for i in range(3)]
    outs = eng.serve(reqs, steps=2)
    assert len(outs) == 3
    assert all(o.tokens.shape == (1, 2) for o in outs)
    assert all(o.buckets == (4,) for o in outs)
    # ragged prompt lengths are admitted now (PR 2): left-pad to the
    # group's length bucket + per-row mask, NOT a ValueError
    outs = eng.serve([{"tokens": jnp.arange(12, dtype=jnp.int32)},
                      {"tokens": jnp.arange(9, dtype=jnp.int32)}], steps=2)
    assert len(outs) == 2
    assert all(o.tokens.shape == (1, 2) for o in outs)
    assert all(bool(jnp.isfinite(o.logits_last.astype(jnp.float32)).all())
               for o in outs)


def test_install_then_engine_start_is_lookup_only(small_model, tmp_path,
                                                 monkeypatch):
    """python -m repro.core.install pre-populates every bucket's plan;
    a subsequent Engine start must be registry lookups only (no tuning)."""
    from repro.core.install import install_arch, serving_problems

    model, params, axes = small_model
    buckets = buckets_for(8)
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans.json"))
    registry.clear_memory()
    try:
        n = install_arch(model.cfg, buckets)
        registry.flush()
        assert n == len(serving_problems(model.cfg, buckets)) > 0

        registry.clear_memory()          # drop memory; file must carry it
        eng = Engine(model, params, axes, max_len=48, max_batch=8,
                     prepack=True)
        stats = registry.stats()
        assert len(eng.pack_report) >= 4
        assert stats["misses"] == 0, stats
        assert stats["hits"] > 0
    finally:
        registry.clear_memory()


def test_sharded_install_then_mesh_engine_all_hit():
    """num_shards threads from the mesh through pre-pack planning: after a
    sharded install sweep, a sharded Engine start is registry-hits-only
    (it used to tune per-shard shapes the sweep never wrote).  Runs in a
    subprocess so the main pytest process keeps its single-device view."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    code = textwrap.dedent("""
        import os, pathlib
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_PLAN_CACHE"] = "/tmp/repro_mesh_plans.json"
        pathlib.Path("/tmp/repro_mesh_plans.json").unlink(missing_ok=True)
        import jax
        from repro.configs import get_reduced_config
        from repro.core import registry
        from repro.core.install import install_arch, sharded_serving_shapes
        from repro.launch.mesh import make_mesh
        from repro.core.plan import buckets_for
        from repro.models.registry import build_model
        from repro.serve.engine import Engine

        cfg = get_reduced_config("qwen1_5_4b").reduced(
            d_model=512, d_ff=1024, num_layers=2, vocab_size=1024,
            num_heads=8, num_kv_heads=8, head_dim=64)
        mesh = make_mesh((4, 2), ("data", "model"))
        sharded = sharded_serving_shapes(cfg, mesh)
        assert any(s > 1 for _, _, s in sharded), sharded
        registry.clear_memory()
        install_arch(cfg, buckets_for(8), mesh=mesh)
        registry.flush()
        registry.clear_memory()          # fresh process; file must carry it
        model = build_model(cfg)
        params, axes = model.init(jax.random.PRNGKey(0))
        eng = Engine(model, params, axes, max_len=48, max_batch=8,
                     mesh=mesh, prepack=True)
        stats = registry.stats()
        assert len(eng.pack_report) >= 4, eng.pack_report
        assert stats["misses"] == 0, stats
        assert stats["hits"] > 0, stats
        print("MESH_ALL_HIT_OK")
    """)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    assert "MESH_ALL_HIT_OK" in out.stdout


def test_bucketed_benchmark_smoke():
    from benchmarks.bucketed_serving import run
    rows = run(max_batch=2, trace=(1, 2), prompt_len=8, steps=2)
    names = [r[0] for r in rows]
    assert any(n.startswith("bucket_") for n in names)
    assert "padded_rows_fixed" in names and "padded_rows_bucketed" in names
