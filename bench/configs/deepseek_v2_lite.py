"""Plain float32 reference of DeepSeek-V2-Lite, as one chip's share of an
expert-parallel deployment, and its lower-precision control.

Straightforward ``jax.numpy`` at ``precision=HIGHEST``, no kernels, no
cache, no batching tricks, in the expanded form of the DeepSeek-V2 paper:

- embedding lookup;
- per layer, a pre-norm RMSNorm and latent attention: a direct Q
  projection to every head's nope and rope dims, the joint KV
  compression ``wkv_a`` to the latent (normed) and a rope key shared by
  the heads, the latent's expansion ``wkv_b`` to per-head nope keys and
  values, YaRN rotary embedding on the rope dims (the two halves of each
  rope slice rotated as a pair), causal softmax at
  (dn + dr) ** -0.5 x mscale(factor, mscale_all_dim) ** 2, the output
  projection;
- a second RMSNorm, then in the first ``first_k_dense`` layers a SwiGLU
  MLP and in every other the expert layer: a float32 router over every
  published expert, softmax, the top ``experts_per_token``, their
  weights renormalised only if ``norm_topk_prob``; the held experts'
  part (experts ``first_expert`` to ``first_expert + num_experts - 1``,
  each a SwiGLU, weighted by its gate) plus the shared experts, one
  SwiGLU of ``num_shared_experts x d_ff_expert``.  Experts not held add
  nothing, as in the program;
- a final RMSNorm and an untied output head.

A model block without latent attention is the dense decoder of
``dense_lm.py``, which this class extends.  Departures from the published model are in the configuration's
``departures``.  Weights come from ``bench.weights`` (seeded, regenerated
one layer at a time), nothing from the program under test, and the
router's choices are the reference's own.  ``quant='fp8'`` is the control:
every weight matrix (token table, router, expert stacks and head
included) rounded to float8 e4m3 with one scale per output column,
everything else as the reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.configs import dense_lm
from bench.configs.dense_lm import HI, _fp8, _rmsnorm

MATRICES = dense_lm.MATRICES + ("wkv_a", "wkv_b", "ws_gate", "ws_up",
                                "ws_down", "router")


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_freqs(m: dict, dim: int):
    """Inverse frequencies of the rope dims, YaRN-scaled when the model
    block sets ``yarn_factor`` (DeepSeek-V2's yarn rotary embedding)."""
    theta = m["rope_theta"]
    freqs = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    factor = m.get("yarn_factor", 0.0)
    if not factor:
        return freqs, 1.0
    orig = m["yarn_original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(
            theta))

    lo = max(math.floor(corr(m["yarn_beta_fast"])), 0)
    hi = min(math.ceil(corr(m["yarn_beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    extra_mask = 1.0 - ramp
    freqs = (freqs / factor) * (1.0 - extra_mask) + freqs * extra_mask
    return freqs, (_mscale(factor, m["yarn_mscale"])
                   / _mscale(factor, m["yarn_mscale_all_dim"]))


def _rope(x, freqs, mscale: float):
    """x: (B, S, H, D) at positions 0..S-1; rotate half against half."""
    s, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos = (jnp.cos(ang) * mscale)[:, None, :]
    sin = (jnp.sin(ang) * mscale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, gate, up, down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, gate, precision=HI))
                   * jnp.dot(x, up, precision=HI), down, precision=HI)


class DeepSeekV2(dense_lm.DenseLM):
    """Reference (or control) over one configuration's ``model`` block,
    with weights from ``seed``."""

    def __init__(self, model: dict, seed: int, *, quant: str = ""):
        super().__init__(model, seed, quant=quant)
        self.first_dense = model.get("first_k_dense", 0)
        self._dense = jax.jit(functools.partial(self._mla_layer, moe=False),
                              static_argnums=1)
        self._moe = jax.jit(functools.partial(self._mla_layer, moe=True))

    def _w(self, path: str, shape: tuple, layer=0, dtype=None):
        """One layer's leaf in float32 (``dtype``: the program's, when it
        is not the configuration's), float8-rounded per output column
        (an expert stack per expert) in the control."""
        w = W.layer_leaf(self.words, path, layer, shape, dtype or self.dtype)
        w = w.astype(jnp.float32)
        if self.quant == "fp8" and path.rsplit("/", 1)[-1] in MATRICES:
            w = _fp8(w, axis=-2)
        return w

    # -- layers ------------------------------------------------------------

    def _attention(self, x, w):
        m = self.m
        d, h, dn = m["d_model"], m["num_heads"], m["head_dim"]
        dr, dv, kvr = m["rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"]
        b, s, _ = x.shape
        q = jnp.dot(x, w("attn/wq", (d, h * (dn + dr))), precision=HI)
        q = q.reshape(b, s, h, dn + dr)
        ckv = jnp.dot(x, w("attn/wkv_a", (d, kvr + dr)), precision=HI)
        c = _rmsnorm(ckv[..., :kvr], m["norm_eps"]) * w("attn/kv_norm",
                                                        (kvr,))
        kv = jnp.dot(c, w("attn/wkv_b", (kvr, h * (dn + dv))), precision=HI)
        kv = kv.reshape(b, s, h, dn + dv)
        freqs, mscale = _yarn_freqs(m, dr)
        q_rope = _rope(q[..., dn:], freqs, mscale)
        k_rope = _rope(ckv[..., None, kvr:], freqs, mscale)     # one head
        scale = (dn + dr) ** -0.5
        if m.get("yarn_factor") and m.get("yarn_mscale_all_dim"):
            scale *= _mscale(m["yarn_factor"], m["yarn_mscale_all_dim"]) ** 2
        sc = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], kv[..., :dn],
                         precision=HI)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0],
                           precision=HI)) * scale
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., dn:], precision=HI)
        return jnp.dot(o.reshape(b, s, h * dv), w("attn/wo", (h * dv, d)),
                       precision=HI)

    def _experts(self, x, w):
        """The held experts' part of the routed output, plus the shared
        experts: x (T, d) -> (T, d)."""
        m = self.m
        d, ffe = m["d_model"], m["d_ff_expert"]
        held, first = m["num_experts"], m.get("first_expert", 0)
        total = m.get("num_experts_total") or held
        probs = jax.nn.softmax(jnp.dot(
            x, w("mlp/router", (d, total), dtype=jnp.float32), precision=HI),
            axis=-1)
        top_p, top_e = jax.lax.top_k(probs, m["experts_per_token"])
        if m.get("norm_topk_prob", True):
            top_p = top_p / top_p.sum(-1, keepdims=True)
        gate_w = w("mlp/w_gate", (held, d, ffe))
        up_w = w("mlp/w_up", (held, d, ffe))
        down_w = w("mlp/w_down", (held, ffe, d))
        out = jnp.zeros_like(x)
        for e in range(held):
            g = jnp.sum(jnp.where(top_e == first + e, top_p, 0.0), axis=-1)
            out = out + g[:, None] * _swiglu(x, gate_w[e], up_w[e],
                                             down_w[e])
        sff = m["num_shared_experts"] * ffe
        if sff:
            out = out + _swiglu(x, w("mlp/ws_gate", (d, sff)),
                                w("mlp/ws_up", (d, sff)),
                                w("mlp/ws_down", (sff, d)))
        return out

    def _mla_layer(self, x, layer, *, moe: bool):
        """One layer: ``moe`` picks the expert layer (``layers/...`` at
        scan index ``layer``) over the dense one (``dense<layer>/...``)."""
        m = self.m
        d, ff, eps = m["d_model"], m["d_ff"], m["norm_eps"]
        prefix, index = ("layers/", layer) if moe else (f"dense{layer}/", 0)

        def w(path, shape, dtype=None):
            return self._w(prefix + path, shape, index, dtype)

        x = x + self._attention(_rmsnorm(x, eps) * w("ln1", (d,)), w)
        hin = _rmsnorm(x, eps) * w("ln2", (d,))
        if moe:
            b, s, _ = x.shape
            return x + self._experts(hin.reshape(b * s, d), w).reshape(
                b, s, d)
        return x + _swiglu(hin, w("mlp/w_gate", (d, ff)),
                           w("mlp/w_up", (d, ff)), w("mlp/w_down", (ff, d)))

    def hidden(self, tokens):
        """(B, S) token ids -> (B, S, d) hidden states after the last
        layer (the final norm is applied with the head)."""
        if not self.m.get("use_mla"):
            return super().hidden(tokens)
        x = self._embed_rows(jnp.asarray(tokens, jnp.int32))
        for layer in range(self.first_dense):
            x = self._dense(x, layer)
        for layer in range(self.m["num_layers"] - self.first_dense):
            x = self._moe(x, jnp.uint32(layer))
        return x


Reference = DeepSeekV2
