"""Plain float32 reference of the dense decoder LM that the configurations
beside this file run, and its lower-precision control.

Straightforward ``jax.numpy`` at ``precision=HIGHEST``, no kernels, no
cache, no batching tricks: embedding lookup; per layer a pre-norm RMSNorm,
Q/K/V projections with bias, rotary position embedding over the whole
head (the two halves of each head rotated as a pair), causal softmax
attention with grouped KV heads, the output projection, a second RMSNorm
and a SwiGLU MLP; a final RMSNorm and an untied output head.  Where these
equations depart from a configuration's published model, the
configuration's ``departures`` say so.

Weights come from ``bench.weights`` (seeded, regenerated one layer at a
time): nothing is taken from the program under test.  Layer by layer, so
that the reference fits beside nothing else on one chip.  ``quant='fp8'``
is the control: every weight matrix (token table and head included)
rounded to float8 e4m3 with one scale per output column, everything else
as the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                      # largest finite float8_e4m3fn
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _fp8(w, axis: int):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rmsnorm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta: float):
    """x: (B, S, H, D) at positions 0..S-1; rotate half against half."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


class DenseLM:
    """Reference (or control) over one configuration's ``model`` block,
    with weights from ``seed``."""

    def __init__(self, model: dict, seed: int, *, quant: str = ""):
        if quant not in ("", "fp8"):
            raise ValueError(f"unknown quantization {quant!r}")
        self.m = model
        self.words = jnp.asarray(W.seed_words(seed))
        self.quant = quant
        self.dtype = jnp.dtype(model["dtype"])
        self._layer = jax.jit(self._layer_fn)
        self._logits = jax.jit(self._logits_fn)

    # -- weights ---------------------------------------------------------

    def _w(self, path: str, shape: tuple, layer=0):
        w = W.layer_leaf(self.words, path, layer, shape, self.dtype)
        w = w.astype(jnp.float32)
        if self.quant == "fp8" and path.rsplit("/", 1)[-1] in MATRICES:
            w = _fp8(w, axis=0)
        return w

    def _embed_rows(self, tokens):
        rows = W.rows_of(self.words, "embed/tok", tokens, self.m["d_model"],
                         self.dtype).astype(jnp.float32)
        if self.quant == "fp8":
            # the table's scale is per embedding row (its output slice)
            rows = _fp8(rows, axis=-1)
        return rows

    # -- layers ------------------------------------------------------------

    def _layer_fn(self, x, layer):
        m = self.m
        d, h, kh, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                            m["head_dim"], m["d_ff"])
        b, s, _ = x.shape
        w = functools.partial(self._w, layer=layer)
        hin = _rmsnorm(x, m["norm_eps"]) * w("layers/ln1", (d,))
        q = jnp.dot(hin, w("layers/attn/wq", (d, h * hd)), precision=HI)
        k = jnp.dot(hin, w("layers/attn/wk", (d, kh * hd)), precision=HI)
        v = jnp.dot(hin, w("layers/attn/wv", (d, kh * hd)), precision=HI)
        if m["qkv_bias"]:
            q = q + w("layers/attn/bq", (h * hd,))
            k = k + w("layers/attn/bk", (kh * hd,))
            v = v + w("layers/attn/bv", (kh * hd,))
        q = _rope(q.reshape(b, s, h, hd), m["rope_theta"])
        k = _rope(k.reshape(b, s, kh, hd), m["rope_theta"])
        v = v.reshape(b, s, kh, hd)
        g = h // kh
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)
        x = x + jnp.dot(o.reshape(b, s, h * hd),
                        w("layers/attn/wo", (h * hd, d)), precision=HI)
        hin = _rmsnorm(x, m["norm_eps"]) * w("layers/ln2", (d,))
        gate = jnp.dot(hin, w("layers/mlp/w_gate", (d, ff)), precision=HI)
        up = jnp.dot(hin, w("layers/mlp/w_up", (d, ff)), precision=HI)
        return x + jnp.dot(jax.nn.silu(gate) * up,
                           w("layers/mlp/w_down", (ff, d)), precision=HI)

    def hidden(self, tokens):
        """(B, S) token ids -> (B, S, d) hidden states after the last
        layer (the final norm is applied with the head)."""
        x = self._embed_rows(jnp.asarray(tokens, jnp.int32))
        for layer in range(self.m["num_layers"]):
            x = self._layer(x, jnp.uint32(layer))
        return x

    # -- head ---------------------------------------------------------------

    def _logits_fn(self, hrows):
        m = self.m
        hrows = _rmsnorm(hrows, m["norm_eps"]) * self._w("final_norm",
                                                         (m["d_model"],))
        head = self._w("embed/head", (m["d_model"], m["vocab_size"]))
        if self.quant == "fp8":
            head = _fp8(head, axis=0)
        return jnp.dot(hrows, head, precision=HI)

    def logits(self, hrows):
        """(R, d) hidden rows -> (R, vocab) float32 logits."""
        return self._logits(hrows)
