"""DeepSeek-V2-Lite as one chip's share of an expert-parallel deployment,
at a small size on the CPU, on weights seeded by ``bench.weights``:

- the served path (``Engine`` + ``ContinuousScheduler``: ``prefill_row``
  into a live cache, then decode through it) against the plain float32
  reference's full forward pass (``bench/configs/deepseek_v2_lite.py``),
  on the logits of every served position;
- the share test: the routed parts of every share of the experts, plus
  the shared experts counted once, equal the uncut layer;
- a served row's output does not depend on the rows beside it, even when
  every row picks the same experts (no token drops);
- YaRN's rope tables against a direct transcription of DeepSeek-V2's
  published formula; without scaling the tables are unchanged.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.linear import serving_ctx
from repro.models import moe as MOE
from repro.models.layers import Yarn, rope_tables, yarn_of
from repro.models.registry import build_model

# DeepSeek-V2-Lite's block at a small width: latent attention with a
# direct Q projection and YaRN, a dense first layer, 4 of 16 routed
# experts held (from the fifth), top-2 unnormalised, 2 shared experts.
# Every projection is at least 512 wide, as packing asks.
MODEL = dict(
    name="dsv2-lite-small", family="moe", num_layers=3, d_model=512,
    num_heads=8, num_kv_heads=8, head_dim=64, d_ff=1024, vocab_size=1024,
    num_experts=4, num_experts_total=16, first_expert=4,
    num_shared_experts=2, experts_per_token=2, d_ff_expert=256,
    norm_topk_prob=False, first_k_dense=1, use_mla=True, kv_lora_rank=512,
    q_lora_rank=0, rope_head_dim=32, v_head_dim=64, rope_theta=10000.0,
    yarn_factor=40.0, yarn_original_max_position_embeddings=4096,
    yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707, norm_eps=1e-6, tie_embeddings=False,
    dtype="bfloat16")
SEED = 2 ** 33 + 16
# widest |served - reference| logit over the served positions, as a share
# of the reference's logit range: bf16 weights and activations through
# 3 layers read about 1%; a cache left unwritten, a wrong rope or scale,
# or the absent experts counted in, read tens of percent
LOGIT_TOL = 0.05
# a prompt of 300 positions: YaRN's slowed rope dims turn by tenths of a
# radian over it where the unscaled ones would turn by tens
MAX_PROMPT, MAX_LEN = 512, 544


def _seeded(model):
    from bench import run as R
    m = build_model(ModelConfig(**model))
    leaves, axes = R.param_leaves(m)
    return m, R.seeded_params(leaves, SEED), axes


def _reference(model, quant=""):
    from bench import check
    return check.reference_class("deepseek_v2_lite")(model, SEED,
                                                     quant=quant)


def _serve(model, prompts, steps):
    """Serve ``prompts`` through the scheduler: the first admitted at the
    pool's opening (clock ``MAX_PROMPT``), the second after three decode
    steps, into the live cache.  Returns per prompt (served tokens, logits of each)."""
    from repro.serve.engine import Engine
    from repro.serve.scheduler import ContinuousScheduler, Request
    m, params, axes = _seeded(model)
    eng = Engine(m, params, axes, max_len=MAX_LEN, max_batch=2,
                 max_prompt=MAX_PROMPT)
    # wkv_a (512 x 544: no block divides 544) and wkv_b stay unpacked
    assert {p.rsplit("/", 1)[-1] for p in eng.pack_report} == {
        "wq", "wo", "w_gate", "w_up", "w_down", "ws_gate", "ws_up",
        "ws_down", "head"}
    seen = []
    program = eng.programs.program

    def recording(*a, **k):
        prog = program(*a, **k)

        def fn(*args):
            logits, cache = prog.fn(*args)
            seen.append(np.asarray(logits[:, -1], np.float32))
            return logits, cache

        return dataclasses.replace(prog, fn=fn)

    eng.programs.program = recording
    sch = ContinuousScheduler(eng)
    sch.open(MAX_PROMPT)
    out = {}
    try:
        for i, prompt in enumerate(prompts):
            (st, _, _), = sch.admit(Request(tokens=prompt,
                                            max_new_tokens=steps))[0]
            out[i] = (st, [seen[-1][0]])
            for _ in range(3) if i == 0 else iter(lambda: sch.active, {}):
                sch.step()
                for s, rows in out.values():
                    if len(s["emitted"]) > len(rows):
                        rows.append(seen[-1][s["row"]])
    finally:
        sch.close()
    return [(np.asarray(st["emitted"]), np.stack(rows))
            for st, rows in out.values()]


def test_served_logits_match_the_reference(tmp_path, monkeypatch):
    """After the install sweep of ``serving_shapes`` the served path plans
    nothing new (no registry miss), and its logits at every served
    position are the reference's."""
    from repro.core import registry
    from repro.core.install import install_arch
    from repro.core.plan import buckets_for, length_buckets_for
    rng = np.random.default_rng(16)
    prompts = [rng.integers(0, MODEL["vocab_size"], n).astype(np.int32)
               for n in (300, 11)]
    steps = 6
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans.json"))
    registry.clear_memory()
    try:
        install_arch(ModelConfig(**MODEL), buckets_for(2),
                     length_buckets_for(MAX_PROMPT))
        registry.flush()
        registry.clear_memory()
        served = _serve(MODEL, prompts, steps)
        assert registry.stats()["misses"] == 0, registry.stats()
    finally:
        registry.clear_memory()
    ref, ctl = _reference(MODEL), _reference(MODEL, quant="fp8")
    worst = {"": 0.0, "fp8": 0.0}
    for prompt, (tokens, logits) in zip(prompts, served):
        assert len(tokens) == steps and len(logits) == steps
        seq = np.concatenate([prompt, tokens[:-1]])[None]
        rows = np.arange(len(prompt) - 1, len(seq[0]))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.logits(ref.hidden(seq)[0, rows]))
            low = np.asarray(ctl.logits(ctl.hidden(seq)[0, rows]))
        span = want.max() - want.min()
        worst[""] = max(worst[""], np.abs(logits - want).max() / span)
        worst["fp8"] = max(worst["fp8"], np.abs(low - want).max() / span)
    assert worst[""] < LOGIT_TOL, worst
    # the same comparison fails the reference in float8 weights
    assert worst["fp8"] > LOGIT_TOL, worst


def _layer(model, layer_params):
    """The MoE leaves of the first expert layer."""
    return jax.tree.map(lambda v: v[0], layer_params["layers"]["mlp"])


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """4 shares of 4 experts out of 16, the shared experts counted once,
    equal the reference's whole layer; each share is the program's
    expert layer told which experts it holds."""
    whole = dict(MODEL, num_experts=16, first_expert=0)
    _, params, _ = _seeded(whole)
    p = jax.tree.map(lambda v: v.astype(jnp.float32), _layer(whole, params))
    cfg = ModelConfig(**whole)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32)
    parts, shared = [], []
    with serving_ctx():
        _, top_p, top_e = MOE.route(p, cfg, x.reshape(16, -1))
        for i in range(4):
            sl = slice(4 * i, 4 * i + 4)
            share = dict(p, w_gate=p["w_gate"][sl], w_up=p["w_up"][sl],
                         w_down=p["w_down"][sl])
            held = dataclasses.replace(cfg, num_experts=4,
                                       first_expert=4 * i)
            out, _ = MOE.moe_apply(share, held, x)
            parts.append(MOE.held_experts(share, held, x.reshape(16, -1),
                                          top_p, top_e).reshape(x.shape))
            shared.append(out - parts[-1])
    # what every share computes alike, counted once
    for s in shared[1:]:
        np.testing.assert_allclose(np.asarray(s), np.asarray(shared[0]),
                                   atol=1e-5)
    total = sum(parts) + shared[0]
    ref = _reference(whole)

    def w(path, shape, dtype=None):
        return ref._w("layers/" + path, shape, 0, dtype)

    with jax.default_matmul_precision("highest"):
        want = ref._experts(x.reshape(16, -1), w).reshape(x.shape)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=0.02 * scale)
    # a share alone is not the layer
    assert float(jnp.abs(parts[0] + shared[0] - want).max()) > 0.1 * scale


def test_a_served_row_is_its_own_in_a_full_batch():
    """Every row picks the same two held experts (a zero router: uniform
    scores, the lowest indices win).  Served, the last row's output alone
    equals its output among 64 rows.  Training's capacity dispatch takes
    40 rows an expert there and drops the last row's routed part, which
    is why serving does not use it.  No shared experts: the output is
    the routed part alone."""
    model = dict(MODEL, first_expert=0, num_shared_experts=0)
    cfg = ModelConfig(**model)
    _, params, _ = _seeded(model)
    p = dict(_layer(MODEL, params))
    p["router"] = jnp.zeros_like(p["router"])
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 1, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)

    def out(rows, **kw):
        return np.asarray(MOE.moe_apply(p, cfg, rows, **kw)[0], np.float32)

    with serving_ctx():
        full, alone = out(x)[-1], out(x[-1:])[0]
    scale = np.abs(alone).max()
    np.testing.assert_allclose(full, alone, atol=0.01 * scale)
    kept, dropped = out(x[-1:], capacity_factor=1.0)[0], out(
        x, capacity_factor=1.0)[-1]
    np.testing.assert_allclose(kept, alone, atol=0.02 * scale)
    assert np.abs(dropped - kept).max() > 0.1 * scale


def _published_tables(positions, dim, base, y: Yarn):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` in numpy."""
    def corr_dim(rot):
        return (dim * math.log(y.original_max_position_embeddings
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    def mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64)
                                 / dim))
    freq_inter = 1.0 / (y.factor * base ** (np.arange(0, dim, 2,
                                                      dtype=np.float64)
                                            / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    freqs = np.outer(positions, inv_freq)
    m = mscale(y.factor, y.mscale) / mscale(y.factor, y.mscale_all_dim)
    return np.cos(freqs) * m, np.sin(freqs) * m


@pytest.mark.parametrize("mscale,mscale_all", [(0.707, 0.707), (1.0, 0.0)])
def test_yarn_tables_match_the_published_formula(mscale, mscale_all):
    y = Yarn(40.0, 4096, 32.0, 1.0, mscale, mscale_all)
    pos = np.asarray([0, 1, 7, 100, 511, 2047, 4095])
    cos, sin = rope_tables(jnp.asarray(pos), 64, 10000.0, y)
    want_cos, want_sin = _published_tables(pos, 64, 10000.0, y)
    np.testing.assert_allclose(np.asarray(cos), want_cos, atol=2e-4)
    np.testing.assert_allclose(np.asarray(sin), want_sin, atol=2e-4)
    # the low dims keep their frequency, the high ones turn 40x slower
    m = want_cos[0, 0]
    plain, _ = rope_tables(jnp.asarray(pos), 64, 10000.0)
    slow, _ = rope_tables(jnp.asarray(pos) / 40.0, 64, 10000.0)
    np.testing.assert_allclose(np.asarray(cos)[:, :10] / m,
                               np.asarray(plain)[:, :10], atol=1e-6)
    np.testing.assert_allclose(np.asarray(cos)[:, 23:] / m,
                               np.asarray(slow)[:, 23:], atol=2e-4)


def test_unscaled_rope_tables_are_unchanged():
    pos = jnp.arange(300)
    for dim, theta in ((128, 5e6), (64, 1e4)):
        half = dim // 2
        freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos.astype(jnp.float32)[..., None] * freqs
        cos, sin = rope_tables(pos, dim, theta)
        assert np.array_equal(np.asarray(cos), np.asarray(jnp.cos(ang)))
        assert np.array_equal(np.asarray(sin), np.asarray(jnp.sin(ang)))


def test_dense_serving_shapes_are_unchanged():
    """The two dense configurations keep the (k, n) set their plans were
    swept for; the latent-attention, shared-expert model lists exactly
    what ``core.linear`` sees."""
    import json
    from pathlib import Path
    from repro.core.install import serving_shapes
    configs = Path(__file__).resolve().parents[1] / "bench" / "configs"

    def shapes(name):
        model = json.loads((configs / f"{name}.json").read_text())["model"]
        return serving_shapes(ModelConfig(**model))

    assert shapes("qwen1.5-4b") == {
        (2560, 2560), (2560, 6912), (6912, 2560), (2560, 151936)}
    assert shapes("glm4-9b-l20") == {
        (4096, 4096), (4096, 256), (4096, 13696), (13696, 4096),
        (4096, 151552)}
    assert shapes("deepseek-v2-lite-ep8") == {
        (2048, 16 * 192), (2048, 512 + 64), (512, 16 * 256), (16 * 128, 2048),
        (2048, 10944), (10944, 2048), (2048, 2816), (2816, 2048),
        (2048, 102400)}


def test_config_is_the_published_model():
    from repro.configs.deepseek_v2_lite import CONFIG, REDUCED
    from repro.models.attention import mla_scale
    assert yarn_of(CONFIG) == Yarn(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    assert mla_scale(CONFIG) == pytest.approx(0.11472, abs=1e-5)
    assert CONFIG.num_experts == CONFIG.num_experts_total == 64
    assert REDUCED.q_lora_rank == 0 and REDUCED.use_mla
    share = dataclasses.replace(CONFIG, num_experts=8).reduced()
    assert (share.num_experts, share.num_experts_total) == (4, 16)
    with pytest.raises(ValueError):
        dataclasses.replace(CONFIG, num_experts=8, first_expert=60)
