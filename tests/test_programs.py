"""ProgramStore (DESIGN.md §13): AOT compile-once serving programs.

Covers the key schema (structure-only, struct/real-array equivalence),
the memory/disk/traced acquisition ladder, executable disk round-trip
parity, and the headline acceptance contract: ``install --precompile``
followed by an Engine RESTART (fresh subprocess) serves first traffic
with zero trace-time programs.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models.registry import build_model
from repro.serve.engine import Engine
from repro.serve.programs import ProgramStore, program_cache_dir

SRC = str(Path(__file__).resolve().parents[1] / "src")


@pytest.fixture(scope="module")
def small():
    cfg = get_reduced_config("qwen1_5_4b")
    model = build_model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    return model, params, axes


def _decode_args(model, params, b=2, max_len=32):
    cache = model.init_cache(b, max_len)
    tok = jnp.zeros((b, 1), jnp.int32)
    return (params, cache, tok)


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------


def test_key_is_structural_and_stable(small, tmp_path):
    model, params, axes = small
    store = ProgramStore(model, cache_dir=tmp_path)
    args = _decode_args(model, params)
    k1 = store.key_for("decode", args, bucket=2, tokens=1)
    k2 = store.key_for("decode", args, bucket=2, tokens=1)
    assert k1 == k2 and k1.startswith("decode_b2_t1_")
    # ShapeDtypeStructs key identically to real arrays (the precompile
    # phase never allocates, yet its cache entries must hit at serve time)
    structs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype), args)
    assert store.key_for("decode", structs, bucket=2, tokens=1) == k1
    # different argument structure -> different key
    assert store.key_for("decode", _decode_args(model, params, b=1),
                         bucket=1, tokens=1) != k1
    # different kind -> different key even for identical args
    assert store.key_for("prefill", args, bucket=2, tokens=1) != k1


def test_env_cache_dir_resolution(monkeypatch):
    monkeypatch.setenv("REPRO_PROGRAM_CACHE", "/tmp/somewhere")
    assert program_cache_dir() == Path("/tmp/somewhere")
    monkeypatch.setenv("REPRO_PROGRAM_CACHE", "off")
    assert program_cache_dir() is None
    # unset: under the compilation cache's directory when one is named,
    # else at the fixed in-checkout path
    monkeypatch.delenv("REPRO_PROGRAM_CACHE")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/jaxcache")
    assert program_cache_dir() == Path("/tmp/jaxcache/repro-programs")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = Path(__file__).resolve().parents[1]
    assert program_cache_dir() == checkout / ".cache" / "programs"


# ---------------------------------------------------------------------------
# acquisition ladder: traced -> memory -> disk
# ---------------------------------------------------------------------------


def test_store_traced_memory_disk_ladder(small, tmp_path):
    model, params, axes = small
    store = ProgramStore(model, cache_dir=tmp_path)
    args = _decode_args(model, params)
    p1 = store.program("decode", args, bucket=2, tokens=1)
    assert p1.cold and p1.source == "traced"
    logits1, _ = p1.fn(*_decode_args(model, params))
    # same store, same key: warm memory handle, not cold, zero cost
    p2 = store.program("decode", _decode_args(model, params),
                       bucket=2, tokens=1)
    assert not p2.cold and p2.source == "memory" and p2.compile_s == 0.0
    assert store.stats()["traced"] == 1 and store.stats()["reused"] == 1
    # a FRESH store over the same cache dir deserializes instead of
    # tracing, is cold (per-store compile accounting), and bit-matches
    store2 = ProgramStore(model, cache_dir=tmp_path)
    p3 = store2.program("decode", _decode_args(model, params),
                        bucket=2, tokens=1)
    assert p3.cold and p3.source == "disk"
    assert store2.stats()["traced"] == 0
    logits3, _ = p3.fn(*_decode_args(model, params))
    np.testing.assert_array_equal(np.asarray(logits1), np.asarray(logits3))


def test_store_persistence_disabled(small):
    model, params, axes = small
    store = ProgramStore(model, cache_dir=False)
    assert store.cache_dir is None
    p = store.program("decode", _decode_args(model, params),
                      bucket=2, tokens=1)
    assert p.source == "traced"


def test_corrupt_cache_entry_recompiles(small, tmp_path):
    model, params, axes = small
    store = ProgramStore(model, cache_dir=tmp_path)
    p = store.program("decode", _decode_args(model, params),
                      bucket=2, tokens=1)
    path = tmp_path / f"{p.key}.prog"
    assert path.exists()
    path.write_bytes(b"not a pickle")
    store2 = ProgramStore(model, cache_dir=tmp_path)
    p2 = store2.program("decode", _decode_args(model, params),
                        bucket=2, tokens=1)
    assert p2.source == "traced"          # fell back, no crash


def test_grammar_version_bump_invalidates_disk_cache(small, tmp_path,
                                                     monkeypatch):
    """The kernel-synthesis grammar version is folded into the program
    key (DESIGN.md §14): bumping it must turn every disk-cached
    executable into a clean miss — recompile, no crash, no stale hit —
    because a grammar change can alter what any tuned plan lowers to."""
    from repro.kernels.variants import grammar

    model, params, axes = small
    store = ProgramStore(model, cache_dir=tmp_path)
    p1 = store.program("decode", _decode_args(model, params),
                       bucket=2, tokens=1)
    assert p1.source == "traced"
    logits1, _ = p1.fn(*_decode_args(model, params))
    # same grammar: a fresh store hits the disk cache
    p2 = ProgramStore(model, cache_dir=tmp_path).program(
        "decode", _decode_args(model, params), bucket=2, tokens=1)
    assert p2.source == "disk" and p2.key == p1.key

    monkeypatch.setattr(grammar, "GRAMMAR_VERSION", "gen-test-bump")
    store3 = ProgramStore(model, cache_dir=tmp_path)
    p3 = store3.program("decode", _decode_args(model, params),
                        bucket=2, tokens=1)
    assert p3.key != p1.key               # structural key moved
    assert p3.source == "traced"          # clean miss: recompiled
    assert store3.stats()["from_disk"] == 0
    logits3, _ = p3.fn(*_decode_args(model, params))
    np.testing.assert_array_equal(np.asarray(logits1), np.asarray(logits3))
    # the old entry is untouched on disk; reverting the bump hits it again
    monkeypatch.undo()
    p4 = ProgramStore(model, cache_dir=tmp_path).program(
        "decode", _decode_args(model, params), bucket=2, tokens=1)
    assert p4.key == p1.key and p4.source == "disk"


# ---------------------------------------------------------------------------
# precompile -> engine: the compile-once acceptance contract
# ---------------------------------------------------------------------------


def test_precompile_grid_then_engine_traces_nothing(small, tmp_path,
                                                    monkeypatch):
    """In-process version: a precompiled grid makes a fresh Engine's
    first traffic (aligned generate, ragged serve, continuous queue)
    pure disk/memory hits."""
    from repro.core.install import precompile_arch
    from repro.serve.scheduler import Request

    model, params, axes = small
    cfg = model.cfg
    rows = precompile_arch(cfg, (1, 2), (8, 16), max_len=64,
                           cache_dir=tmp_path)
    assert all(r["source"] == "traced" for r in rows)
    kinds = {r["kind"] for r in rows}
    assert kinds == {"prefill", "decode", "prefill_row"}

    eng = Engine(build_model(cfg), params, axes, max_len=64, buckets=(1, 2),
                 max_prompt=16, program_cache=tmp_path)
    rng = np.random.default_rng(0)
    eng.generate({"tokens": np.asarray(rng.integers(0, 512, (2, 8)),
                                       np.int32)}, steps=3)
    eng.serve([{"tokens": np.asarray(rng.integers(0, 512, 5), np.int32)},
               {"tokens": np.asarray(rng.integers(0, 512, 11), np.int32)}],
              steps=2)
    eng.serve_queue([Request(tokens=np.asarray(rng.integers(0, 512, n),
                                               np.int32),
                             max_new_tokens=2, rid=i)
                     for i, n in enumerate((5, 12))])
    st = eng.programs.stats()
    assert st["traced"] == 0, st
    assert st["from_disk"] > 0


def test_install_precompile_then_engine_restart_subprocess(tmp_path):
    """The full restart story: ``install --precompile`` in one process,
    an Engine in a SECOND process (cold jit caches, cold XLA) serves
    first traffic with zero trace-time programs."""
    env = dict(os.environ, PYTHONPATH=SRC,
               REPRO_PROGRAM_CACHE=str(tmp_path / "programs"),
               REPRO_PLAN_CACHE=str(tmp_path / "plans.json"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"))

    install = subprocess.run(
        [sys.executable, "-m", "repro.core.install", "--precompile",
         "--reduced", "--archs", "qwen1_5_4b", "--max-batch", "2",
         "--max-prompt", "16", "--max-len", "64"],
        capture_output=True, text=True, timeout=900, env=env)
    assert install.returncode == 0, install.stderr[-4000:]
    assert "precompiled serving grids" in install.stdout

    serve = textwrap.dedent("""
        import jax, numpy as np
        from repro.configs import get_reduced_config
        from repro.models.registry import build_model
        from repro.serve.engine import Engine
        from repro.serve.scheduler import Request
        cfg = get_reduced_config("qwen1_5_4b")
        model = build_model(cfg)
        params, axes = model.init(jax.random.PRNGKey(0))
        eng = Engine(model, params, axes, max_len=64, buckets=(1, 2),
                     max_prompt=16)
        rng = np.random.default_rng(0)
        eng.generate({"tokens": np.asarray(rng.integers(0, 512, (2, 8)),
                                           np.int32)}, steps=3)
        eng.serve_queue([Request(tokens=np.asarray(
            rng.integers(0, 512, n), np.int32), max_new_tokens=2, rid=i)
            for i, n in enumerate((5, 12))])
        st = eng.programs.stats()
        assert st["traced"] == 0, st
        assert st["from_disk"] > 0, st
        print("RESTART-OK", st["from_disk"], "programs from disk")
    """)
    out = subprocess.run([sys.executable, "-c", serve], capture_output=True,
                         text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "RESTART-OK" in out.stdout
