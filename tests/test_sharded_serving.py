"""Tensor-parallel serving as a first-class Engine mode (DESIGN.md §13).

Each test runs in a fresh subprocess with 8 forced host devices (same
harness as test_distributed.py) so the main pytest process keeps its
single-device view.  Pins:

* token-for-token parity: a sharded Engine (mesh model=2) reproduces the
  single-device engine's greedy decode exactly — for the aligned
  ``generate`` path AND the continuous-batching queue (sharded
  ``prefill_row`` admission into a sharded live cache);
* the collective contract: the stored sharded decode program moves a
  FIXED set of collectives per step (the CI budget — a regression that
  adds resharding traffic fails this exactly).
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_sub(body: str, timeout=900) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_PLAN_CACHE"] = "/tmp/repro_sub_plans.json"
        os.environ.setdefault("REPRO_PROGRAM_CACHE", "off")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.configs import get_reduced_config
        from repro.models.registry import build_model
        from repro.serve.engine import Engine
        from repro.serve.scheduler import Request
        from repro.sharding.rules import ShardingOptions

        cfg = get_reduced_config("qwen1_5_4b").reduced(dtype="float32")
        params, axes = build_model(cfg).init(jax.random.PRNGKey(0))
        mesh = make_mesh((2,), ("model",))
        opts = ShardingOptions(dp_axes=())
        eng = Engine(build_model(cfg), params, axes, max_len=64,
                     buckets=(1, 2), max_prompt=16, mesh=mesh, opts=opts)
        assert eng.sharded
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_sharded_generate_parity_token_for_token():
    out = run_sub("""
        ref = Engine(build_model(cfg), params, axes, max_len=64,
                     buckets=(1, 2), max_prompt=16)
        rng = np.random.default_rng(0)
        batch = {"tokens": np.asarray(rng.integers(0, 512, (2, 8)),
                                      np.int32)}
        res = eng.generate(batch, steps=6)
        res0 = ref.generate(batch, steps=6)
        assert np.array_equal(np.asarray(res.tokens),
                              np.asarray(res0.tokens))
        # params/cache actually live distributed (not a replicated sham):
        # at least one param leaf spans both devices
        leaves = jax.tree.leaves(eng.params)
        assert any(len(x.sharding.device_set) == 2 for x in leaves)
        print("OK sharded generate parity")
    """)
    assert "OK sharded generate parity" in out


def test_sharded_queue_parity_token_for_token():
    out = run_sub("""
        ref = Engine(build_model(cfg), params, axes, max_len=64,
                     buckets=(1, 2), max_prompt=16)
        def queue():
            rng = np.random.default_rng(1)
            return [Request(tokens=np.asarray(rng.integers(0, 512, n),
                                              np.int32),
                            max_new_tokens=m, rid=i)
                    for i, (n, m) in enumerate([(5, 3), (12, 2), (9, 4)])]
        res, stats = eng.serve_queue(queue())
        res0, stats0 = ref.serve_queue(queue())
        for a, b in zip(res, res0):
            assert np.array_equal(a.tokens, b.tokens), (a.rid, a.tokens,
                                                        b.tokens)
        assert stats.admitted == stats0.admitted == 3
        print("OK sharded queue parity")
    """)
    assert "OK sharded queue parity" in out


def test_sharded_kernels_read_layer_of_stacked_weights():
    """Under a mesh each planned kernel runs per shard (``shard_map``)
    and reads its layer of the stacked packed weight by the scan's layer
    index: the sharded engine's Pallas decode reproduces the
    single-device one token for token."""
    out = run_sub("""
        os.environ["REPRO_TSMM_IMPL"] = "pallas_interpret"
        cfg = get_reduced_config("qwen1_5_4b").reduced(
            d_model=512, d_ff=1024, num_layers=2, vocab_size=1024,
            num_heads=8, num_kv_heads=8, head_dim=64, dtype="float32")
        params, axes = build_model(cfg).init(jax.random.PRNGKey(0))
        kw = dict(max_len=32, buckets=(2,), max_prompt=8)
        sh = Engine(build_model(cfg), params, axes, mesh=mesh, opts=opts,
                    **kw)
        ref = Engine(build_model(cfg), params, axes, **kw)
        assert any(k.startswith("layers") for k in sh.pack_report)
        batch = {"tokens": np.asarray(
            np.random.default_rng(0).integers(0, 1024, (2, 8)), np.int32)}
        res, res0 = sh.generate(batch, steps=3), ref.generate(batch, steps=3)
        assert np.array_equal(np.asarray(res.tokens), np.asarray(res0.tokens))
        print("OK sharded layer-view kernels")
    """)
    assert "OK sharded layer-view kernels" in out


def test_sharded_decode_collective_contract():
    """The CI contract: per decode step the stored TP program performs
    EXACTLY 3 all-reduces (attention out / MLP down projections, XLA-
    fused across the 2-layer scan) moving 5120 bytes and 1 logits
    all-gather moving 2048 bytes per device — and never an all-to-all or
    reduce-scatter.  Any resharding regression changes these numbers."""
    out = run_sub("""
        rng = np.random.default_rng(0)
        eng.generate({"tokens": np.asarray(rng.integers(0, 512, (2, 8)),
                                           np.int32)}, steps=2)
        dprog = [p for p in eng.programs._programs.values()
                 if p.kind == "decode"][0]
        col = eng.programs.collectives(dprog)
        assert col["all-reduce"]["count"] == 3, col
        assert col["all-reduce"]["bytes_moved"] == 5120.0, col
        assert col["all-gather"]["count"] == 1, col
        assert col["all-gather"]["bytes_moved"] == 2048.0, col
        assert "all-to-all" not in col and "reduce-scatter" not in col, col
        print("OK collective contract", col)
    """)
    assert "OK collective contract" in out


def test_sharded_precompile_restart_zero_traces(tmp_path):
    """Sharded programs round-trip the disk cache too: precompile on the
    8-device host, restart, serve sharded with zero traces."""
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               REPRO_PLAN_CACHE="/tmp/repro_sub_plans.json",
               REPRO_PROGRAM_CACHE=str(tmp_path / "programs"))
    body = textwrap.dedent("""
        import jax, numpy as np
        from repro.configs import get_reduced_config
        from repro.models.registry import build_model
        from repro.serve.engine import Engine
        from repro.launch.mesh import make_mesh
        from repro.sharding.rules import ShardingOptions

        cfg = get_reduced_config("qwen1_5_4b").reduced(dtype="float32")
        params, axes = build_model(cfg).init(jax.random.PRNGKey(0))
        mesh = make_mesh((2,), ("model",))
        opts = ShardingOptions(dp_axes=())
        eng = Engine(build_model(cfg), params, axes, max_len=64,
                     buckets=(2,), max_prompt=16, mesh=mesh, opts=opts)
        rng = np.random.default_rng(0)
        eng.generate({"tokens": np.asarray(rng.integers(0, 512, (2, 8)),
                                           np.int32)}, steps=2)
        st = eng.programs.stats()
        print("STATS", st["traced"], st["from_disk"])
    """)
    first = subprocess.run([sys.executable, "-c", body],
                           capture_output=True, text=True, timeout=900,
                           env=env)
    assert first.returncode == 0, first.stderr[-4000:]
    assert "STATS 2 0" in first.stdout      # cold host: traced programs
    second = subprocess.run([sys.executable, "-c", body],
                            capture_output=True, text=True, timeout=900,
                            env=env)
    assert second.returncode == 0, second.stderr[-4000:]
    assert "STATS 0 2" in second.stdout     # restart: disk only, no traces
