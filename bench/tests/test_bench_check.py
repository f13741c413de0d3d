"""What decides ``correct``: the reference, its control and the faults.

The faults drive a whole run at a tiny size on the CPU (the harness's
look for a chip skipped) with the served path broken underneath, and
need ``correct`` to come out false against the cells' own limit."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, weights as W
from bench.configs.dense_lm import DenseLM

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = ROOT / "bench" / "configs"
LIMIT = min(json.loads(p.read_text())["check"]["served_logit_gap"]
            for p in CONFIGS.glob("*.json"))
SMALL = dict(num_layers=2, d_model=512, num_heads=4, num_kv_heads=2,
             head_dim=128, d_ff=1024, vocab_size=2048, qkv_bias=True,
             rope_theta=1e4, norm_eps=1e-6, dtype="bfloat16")
# the control's sizes: each configuration's attention (MHA with
# Qwen1.5's RoPE base; GQA with 2 KV heads and GLM-4's norm epsilon) at
# 8 layers of width 1024, which a test run holds
CONTROL = {
    "mha": dict(SMALL, num_layers=8, d_model=1024, num_heads=8,
                num_kv_heads=8, d_ff=2816, vocab_size=8192, rope_theta=5e6),
    "gqa": dict(SMALL, num_layers=8, d_model=1024, num_heads=8,
                num_kv_heads=2, d_ff=2816, vocab_size=8192,
                norm_eps=1.5625e-7),
}


def _drive(tmp_path, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "tests" / "drive_tiny.py"),
         "--state", str(tmp_path), "--fault", fault, "--limit", str(LIMIT)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["", "token", "stale_cache"])
def test_a_fault_in_the_served_path_is_not_correct(tmp_path, fault):
    res = _drive(tmp_path, fault)
    gap = res["checks"]["served_logit_gap"]["value"]
    assert res["failed"] == 0
    if fault:
        assert not res["correct"] and gap > LIMIT
    else:
        assert res["correct"] and gap <= LIMIT


def test_weights_of_one_layer_match_the_stacked_tree():
    words = jnp.asarray(W.seed_words(2 ** 33 + 1))
    leaves = {"layers/attn/wq": ((3, 64, 128), jnp.bfloat16, True),
              "embed/tok": ((50, 64), jnp.bfloat16, False)}
    tree = jax.jit(lambda w: W.make_tree(w, leaves))(words)
    for layer in range(3):
        one = W.layer_leaf(words, "layers/attn/wq", jnp.uint32(layer),
                           (64, 128), jnp.bfloat16)
        assert np.array_equal(np.asarray(one), np.asarray(
            tree["layers/attn/wq"][layer]))
    rows = W.rows_of(words, "embed/tok", jnp.asarray([3, 49]), 64,
                     jnp.bfloat16)
    assert np.array_equal(np.asarray(rows),
                          np.asarray(tree["embed/tok"][jnp.asarray([3, 49])]))
    std = float(np.asarray(tree["layers/attn/wq"], np.float32).std())
    assert abs(std - 1 / 8) < 0.01


def test_reference_ranks_its_own_choice_first():
    rng = np.random.default_rng(5)
    seqs = [(rng.integers(0, 2048, 20).astype(np.int32),
             rng.integers(0, 2048, 12).astype(np.int32)) for _ in range(2)]
    ref = DenseLM(SMALL, 11)
    with jax.default_matmul_precision("highest"):
        assert check.control_gaps(ref, ref, seqs, 2, 32).max() == 0.0
        picks = check.first_choices(ref, seqs, 2, 32)
        mine = [(p, picks[12 * i:12 * (i + 1)]) for i, (p, _) in
                enumerate(seqs)]
        # served tokens that are the reference's own choices read 0 only
        # where the context agrees: the first position of each sequence
        gaps = check.served_gaps(ref, mine, 2, 32)
    assert gaps[0] == 0.0 and gaps[12] == 0.0


@pytest.mark.parametrize("shape", sorted(CONTROL))
def test_control_in_float8_is_not_correct(shape):
    """The reference in float8 weights, read at every position of 8
    sequences of 128 tokens, puts a token first that the float32
    reference ranks below its best by more than the cells' limit."""
    m = CONTROL[shape]
    rng = np.random.default_rng(3)
    seqs = [(rng.integers(0, m["vocab_size"], 64).astype(np.int32),
             rng.integers(0, m["vocab_size"], 64).astype(np.int32))
            for _ in range(8)]
    ref, ctl = DenseLM(m, 3), DenseLM(m, 3, quant="fp8")
    with jax.default_matmul_precision("highest"):
        gaps = check.control_gaps(ref, ctl, seqs, 8, 128)
    assert gaps.max() > LIMIT
