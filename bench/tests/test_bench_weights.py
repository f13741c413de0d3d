"""``bench/weights.py``: the seeded init of every family the model block
describes.  The dense configurations keep the standard deviation of every
leaf (so their weights, a pure function of it, are bit for bit as
before); a latent-attention, routed-expert tree gets norm scales of one
and expert stacks at their own fan-in."""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as R
from bench import weights as W

CONFIGS = Path(__file__).resolve().parents[1] / "configs"

# leaf_std of every leaf at published widths, as the benchmark's first
# version of this module gave them (shape without the layer dim)
DENSE_STD = {
    "qwen1.5-4b": {
        "embed/head": 0.01976423537605237, "embed/tok": 0.02,
        "final_norm": 0.0, "layers/attn/bk": 0.1, "layers/attn/bq": 0.1,
        "layers/attn/bv": 0.1, "layers/attn/wk": 0.01976423537605237,
        "layers/attn/wo": 0.01976423537605237,
        "layers/attn/wq": 0.01976423537605237,
        "layers/attn/wv": 0.01976423537605237, "layers/ln1": 0.0,
        "layers/ln2": 0.0, "layers/mlp/w_down": 0.012028130608117204,
        "layers/mlp/w_gate": 0.01976423537605237,
        "layers/mlp/w_up": 0.01976423537605237},
    "glm4-9b-l20": {
        "embed/head": 0.015625, "embed/tok": 0.02, "final_norm": 0.0,
        "layers/attn/bk": 0.1, "layers/attn/bq": 0.1, "layers/attn/bv": 0.1,
        "layers/attn/wk": 0.015625, "layers/attn/wo": 0.015625,
        "layers/attn/wq": 0.015625, "layers/attn/wv": 0.015625,
        "layers/ln1": 0.0, "layers/ln2": 0.0,
        "layers/mlp/w_down": 0.008544824087808291,
        "layers/mlp/w_gate": 0.015625, "layers/mlp/w_up": 0.015625},
}


def _model(spec):
    from repro.configs.base import ModelConfig
    from repro.models.registry import build_model
    return build_model(spec if isinstance(spec, ModelConfig)
                       else ModelConfig(**spec))


def _std_table(leaves):
    return {p: W.leaf_std(p, shape[1:] if stacked else shape)
            for p, (shape, _, stacked) in leaves.items()}


@pytest.mark.parametrize("name", sorted(DENSE_STD))
def test_dense_leaf_std_is_unchanged(name):
    spec = json.loads((CONFIGS / f"{name}.json").read_text())["model"]
    leaves, _ = R.param_leaves(_model(spec))
    assert _std_table(leaves) == DENSE_STD[name]


@pytest.fixture(scope="module")
def deepseek():
    """The repository's reduced DeepSeek-V2 (latent attention with a Q
    LoRA, one leading dense layer, 8 routed experts top-2 and a shared
    expert) through the harness's weight path."""
    from repro.configs.deepseek_v2_236b import REDUCED
    leaves, _ = R.param_leaves(_model(REDUCED))
    return REDUCED, leaves, R.seeded_params(leaves, 2 ** 33 + 7)


def _leaf(params, path):
    for k in path.split("/"):
        params = params[k]
    return np.asarray(params, np.float32)


def test_latent_norms_are_one_and_experts_at_fan_in(deepseek):
    _, leaves, params = deepseek
    names = {p.rsplit("/", 1)[-1] for p in leaves}
    assert {"q_norm", "kv_norm", "ln1", "ln2", "final_norm"} <= names
    experts = 0
    for path, (shape, _, stacked) in leaves.items():
        name = path.rsplit("/", 1)[-1]
        v = _leaf(params, path)
        if name in ("ln1", "ln2") or name.endswith("norm"):
            assert np.all(v == 1.0), path
        elif len(shape) - stacked == 3:             # (E, k, n) expert stack
            experts += 1
            want = 1 / math.sqrt(shape[-2])
            assert abs(v.std() / want - 1) < 0.05, (path, v.std(), want)
    assert experts == 3


def test_attention_and_experts_outputs_of_one_scale(deepseek):
    """On inputs of unit RMS, the latent-attention and the MoE sublayers
    of a layer give outputs within a factor of 30 of each other in RMS,
    so that neither is rounded away in the other's bf16 residual."""
    from repro.models import attention as A
    from repro.models import moe as MOE
    cfg, _, params = deepseek
    lp = jax.tree.map(lambda v: v[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.d_model))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))
    x = x.astype(jnp.bfloat16)
    attn, _ = A.mla_forward(lp["attn"], cfg, x)
    moe, _ = MOE.moe_apply(lp["mlp"], cfg, x)

    def rms(v):
        return float(jnp.sqrt(jnp.mean(jnp.square(v.astype(jnp.float32)))))

    ratio = rms(attn) / rms(moe)
    assert 1 / 30 < ratio < 30, (rms(attn), rms(moe))
