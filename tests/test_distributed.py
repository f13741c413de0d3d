"""Distributed-path tests.  Each test runs in a fresh subprocess with
``xla_force_host_platform_device_count=8`` so the main pytest process keeps
its single-device view (per the assignment brief: never set the flag
globally)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_sub(body: str, timeout=900) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_PLAN_CACHE"] = "/tmp/repro_sub_plans.json"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_distributed_tsmm_no_collectives_and_correct():
    out = run_sub("""
        from repro.core import tsmm as T
        from repro.kernels import ref
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((1024, 512)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((512, 16)), jnp.float32)
        got = T.distributed_tsmm(a, b, mesh, "data")
        want = ref.tsmm_ref(a, b)
        err = float(jnp.abs(got - want).max())
        assert err < 1e-3, err
        # GEBB_t property: zero cross-device collectives in the fwd path
        fn = lambda x, y: T.distributed_tsmm(x, y, mesh, "data")
        txt = jax.jit(fn).lower(a, b).compile().as_text()
        for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all"):
            assert op not in txt, op
        print("OK no-collective distributed tsmm, err", err)
    """)
    assert "OK no-collective" in out


def test_conventional_ksplit_has_allreduce():
    out = run_sub("""
        from repro.core import tsmm as T
        from repro.kernels import ref
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((256, 1024)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((1024, 16)), jnp.float32)
        got = T.conventional_ksplit(a, b, mesh, "data")
        want = ref.tsmm_ref(a, b)
        assert float(jnp.abs(got - want).max()) < 1e-3
        txt = jax.jit(lambda x, y: T.conventional_ksplit(x, y, mesh, "data")).lower(a, b).compile().as_text()
        assert "all-reduce" in txt
        print("OK ksplit correct + all-reduce present")
    """)
    assert "OK ksplit" in out


def test_overlapped_ring_tsmm_correct():
    out = run_sub("""
        from repro.core import tsmm as T
        from repro.kernels import ref
        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(1)
        a = jnp.asarray(rng.standard_normal((128, 1024)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((1024, 32)), jnp.float32)
        got = T.overlapped_ring_tsmm(a, b, mesh, "data")
        want = ref.tsmm_ref(a, b)
        assert float(jnp.abs(got - want).max()) < 1e-3
        txt = jax.jit(lambda x, y: T.overlapped_ring_tsmm(x, y, mesh, "data")).lower(a, b).compile().as_text()
        assert "collective-permute" in txt
        print("OK ring tsmm correct + ppermute present")
    """)
    assert "OK ring" in out


def test_packed_kernel_runs_per_shard_under_mesh():
    """XLA cannot partition a Mosaic kernel, so under a serving mesh a
    packed weight's kernel runs once per shard: column-sharded blocks
    give each device its own output columns, row-sharded blocks sum f32
    partials across the axis.  Both match the unsharded product."""
    out = run_sub("""
        from repro.core.packing import pack
        from repro.core.tsmm import tsmm_dot
        from repro.sharding.context import sharding_ctx
        from repro.sharding.rules import ShardingOptions
        mesh = make_mesh((4,), ("model",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((8, 1024)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((1024, 512)), jnp.float32)
        bias = jnp.asarray(rng.standard_normal((512,)), jnp.float32)
        want = np.asarray(jax.nn.silu(x @ w + bias))
        fn = jax.jit(lambda x, w, b: tsmm_dot(x, w, bias=b, act="silu",
                                              impl="pallas_interpret"))
        for axes in (("model", None), (None, "model")):
            pk = pack(w, 256, 128)
            pk.shard_axes = axes
            pk.blocks = jax.device_put(
                pk.blocks, NamedSharding(mesh, P(*axes, None, None)))
            with sharding_ctx(mesh, ShardingOptions(dp_axes=())):
                got = np.asarray(fn(x, pk, bias))
                txt = fn.lower(x, pk, bias).compile().as_text()
            err = float(np.abs(got - want).max())
            assert err < 1e-3 * float(np.abs(want).max()), (axes, err)
            assert ("all-reduce" in txt) == (axes[0] is not None), axes
        print("OK per-shard packed kernel")
    """)
    assert "OK per-shard packed kernel" in out


def test_sharded_train_step_runs_and_matches_single():
    out = run_sub("""
        from repro.configs import get_reduced_config
        from repro.models.registry import build_model
        from repro.optim.adamw import OptConfig
        from repro.train.step import init_train_state, make_train_step
        from repro.launch.specs import train_state_specs
        from repro.sharding.context import sharding_ctx
        from repro.sharding.rules import ShardingOptions

        cfg = get_reduced_config('glm4_9b').reduced(
            d_model=128, d_ff=256, num_layers=2, vocab_size=512,
            num_heads=4, num_kv_heads=2, head_dim=32)
        model = build_model(cfg)
        ocfg = OptConfig(warmup_steps=0, decay_steps=10)
        mesh = make_mesh((4, 2), ("data", "model"))
        opts = ShardingOptions(dp_axes=("data",), fsdp=True)
        batch = {"tokens": (jnp.arange(8*32).reshape(8, 32) % 512).astype(jnp.int32),
                 "labels": (jnp.arange(8*32).reshape(8, 32) % 512).astype(jnp.int32)}

        # single-device reference
        state, _ = init_train_state(model, ocfg, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(model, ocfg))
        _, m_ref = step(state, batch)

        # sharded
        with sharding_ctx(mesh, opts):
            state2, _ = init_train_state(model, ocfg, jax.random.PRNGKey(0))
            _, sh, _ = train_state_specs(model, ocfg, mesh, opts)
            state2 = jax.tree.map(lambda x, s: jax.device_put(x, s), state2, sh)
            step2 = jax.jit(make_train_step(model, ocfg), in_shardings=(sh, None))
            _, m_sh = step2(state2, batch)
        d = abs(float(m_ref["loss"]) - float(m_sh["loss"]))
        assert d < 2e-2, d
        print("OK sharded train step, loss delta", d)
    """)
    assert "OK sharded train step" in out


def test_elastic_remesh_and_continue():
    out = run_sub("""
        from repro.train.loop import make_elastic_mesh
        from repro.core.autotuner import make_plan
        from repro.core.plan import Problem
        devs = jax.devices()
        m8 = make_elastic_mesh(devs, tp=2)
        assert dict(m8.shape) == {"data": 4, "model": 2}
        # simulate losing 2 devices -> 6 usable -> 3x2 mesh
        m6 = make_elastic_mesh(devs[:6], tp=2)
        assert dict(m6.shape) == {"data": 3, "model": 2}
        # plans are keyed by shard count: re-plan is a lookup/miss, not a crash
        p8 = make_plan(Problem(4096, 1024, 16, "float32", num_shards=8), persist=False)
        p6 = make_plan(Problem(4096, 1024, 16, "float32", num_shards=6), persist=False)
        assert p8.problem.num_shards == 8 and p6.problem.num_shards == 6
        print("OK elastic remesh")
    """)
    assert "OK elastic remesh" in out


def test_dryrun_cell_on_8_devices():
    """End-to-end mini dry-run (2x4 mesh) through the real run_cell code."""
    out = run_sub("""
        import repro.launch.dryrun as dr
        from pathlib import Path
        import tempfile, json
        dr.ART_DIR = Path(tempfile.mkdtemp())
        import repro.launch.mesh as lm
        lm.make_production_mesh = lambda multi_pod=False: make_mesh(
            (2, 2, 2), ("pod", "data", "model")) if multi_pod else make_mesh((4, 2), ("data", "model"))
        rec = dr.run_cell("whisper_base", "train_4k", "single", force=True)
        assert rec["cost_analysis"].get("flops", 0) > 0
        assert "jaxpr_cost" in rec and rec["jaxpr_cost"]["flops"] > 0
        rec2 = dr.run_cell("mamba2_780m", "long_500k", "multi", force=True)
        assert rec2["kind"] == "decode"
        print("OK mini dryrun", rec["jaxpr_cost"]["flops"] > rec["cost_analysis"]["flops"])
    """, timeout=1200)
    assert "OK mini dryrun" in out
