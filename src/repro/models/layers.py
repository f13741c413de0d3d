"""Shared layers: norms, RoPE, embeddings, MLPs."""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.linear import linear
from repro.models.param import ParamTree
from repro.sharding.context import shard_act


@jax.named_scope("norm")
def rmsnorm(x, scale, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layernorm(x, scale, bias, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def silu(x):
    return x * jax.nn.sigmoid(x)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


class Yarn(NamedTuple):
    """YaRN rope scaling as DeepSeek-V2 publishes it (``rope_scaling``)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


def yarn_of(cfg) -> Optional[Yarn]:
    """``cfg``'s YaRN scaling, or None without one."""
    if not cfg.yarn_factor:
        return None
    return Yarn(cfg.yarn_factor, cfg.yarn_original_max_position_embeddings,
                cfg.yarn_beta_fast, cfg.yarn_beta_slow, cfg.yarn_mscale,
                cfg.yarn_mscale_all_dim)


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_dim(rotations: float, dim: int, theta: float, original: int):
    """The dim whose wavelength makes ``rotations`` turns over the
    original context (``yarn_find_correction_dim``)."""
    return (dim * math.log(original / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


@jax.named_scope("rope")
def rope_tables(positions, dim: int, theta: float,
                yarn: Optional[Yarn] = None):
    """cos/sin tables for given integer positions (any shape).

    With ``yarn`` (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``): a
    linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow`` blends each base frequency with itself over
    ``factor``, and the tables are multiplied by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    half = dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if yarn is not None:
        orig = yarn.original_max_position_embeddings
        lo = max(math.floor(_yarn_dim(yarn.beta_fast, dim, theta, orig)), 0)
        hi = min(math.ceil(_yarn_dim(yarn.beta_slow, dim, theta, orig)),
                 dim - 1)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo)
                        / ((hi - lo) or 0.001), 0.0, 1.0)
        freqs = freqs / yarn.factor * ramp + freqs * (1.0 - ramp)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    return cos, sin


@jax.named_scope("rope")
def apply_rope(x, cos, sin):
    """x: (..., S, H, D); cos/sin: (S, D/2) or broadcastable (..., S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # (S, half) -> broadcast over batch + heads
        cos = cos[:, None, :]
        sin = sin[:, None, :]
    else:  # (B, S, half)
        cos = cos[..., None, :]
        sin = sin[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
                           ).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU + plain GELU variants)
# ---------------------------------------------------------------------------


def init_swiglu(rng, d_model: int, d_ff: int, dtype, d_out: int = 0):
    pt = ParamTree(rng, dtype)
    pt.dense("w_gate", (d_model, d_ff), ("embed", "mlp"))
    pt.dense("w_up", (d_model, d_ff), ("embed", "mlp"))
    pt.dense("w_down", (d_ff, d_out or d_model), ("mlp", "embed"))
    return pt.build()


@jax.named_scope("mlp")
def swiglu(p, x):
    h = (linear(x, p["w_gate"], act="silu", name="w_gate")
         * linear(x, p["w_up"], name="w_up"))
    h = shard_act(h, "batch", "seq", "mlp")
    return linear(h, p["w_down"], name="w_down")


def init_gelu_mlp(rng, d_model: int, d_ff: int, dtype, d_out: int = 0):
    pt = ParamTree(rng, dtype)
    pt.dense("w_in", (d_model, d_ff), ("embed", "mlp"))
    pt.zeros("b_in", (d_ff,), ("mlp",))
    pt.dense("w_out", (d_ff, d_out or d_model), ("mlp", "embed"))
    pt.zeros("b_out", (d_out or d_model,), ("embed",))
    return pt.build()


def sinusoidal_pos(positions, dim: int):
    """Fixed sinusoidal position encoding (whisper stub adaptation: the
    reference model uses learned decoder embeddings; sinusoidal keeps the
    param shapes independent of max sequence length)."""
    half = dim // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def gelu_mlp(p, x):
    h = linear(x, p["w_in"], p["b_in"], act="gelu", name="w_in")
    h = shard_act(h, "batch", "seq", "mlp")
    return linear(h, p["w_out"], p["b_out"], name="w_out")


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def init_embed(rng, vocab: int, d_model: int, dtype, tie: bool):
    pt = ParamTree(rng, dtype)
    pt.embed("tok", (vocab, d_model), ("vocab", "embed"))
    if not tie:
        pt.dense("head", (d_model, vocab), ("embed", "vocab"))
    return pt.build()


@jax.named_scope("embed")
def embed_tokens(p, tokens):
    out = jnp.take(p["tok"], tokens, axis=0)
    return shard_act(out, "batch", "seq", "embed")


def unembed(p, x, tie: bool):
    # logits stay in compute dtype; losses upcast internally.  bf16 logits
    # keep the backward cotangent chain bf16 (halves every TP activation
    # all-reduce in the backward pass — §Perf B4) and halve the logits
    # buffer (B x S x vocab is the largest activation in the program).
    w = p["tok"].T if tie else p["head"]
    logits = linear(x, w, name="head")
    return shard_act(logits, "batch", "seq", "vocab")
