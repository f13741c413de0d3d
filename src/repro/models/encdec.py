"""Whisper-style encoder-decoder backbone.

The conv/mel frontend is a STUB per the brief: inputs are precomputed
frame embeddings (B, enc_seq, d_model).  Positions use fixed sinusoidal
encodings (adaptation: reference uses learned decoder embeddings — see
layers.sinusoidal_pos docstring).  Cross-attention K/V are computed once
per utterance at prefill and cached — the clearest in-model instance of
the paper's pre-pack-and-reuse pattern (the encoder output is 'packed'
into per-layer K/V exactly once, then reused for every decoded token).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models.layers import (embed_tokens, gelu_mlp, init_embed,
                                 init_gelu_mlp, layernorm, sinusoidal_pos,
                                 unembed)
from repro.models.param import ParamTree, stack_inits
from repro.sharding.context import shard_act


def _ln(pt, name, d):
    pt.ones(f"{name}_s", (d,), ("embed",))
    pt.zeros(f"{name}_b", (d,), ("embed",))


def _apply_ln(p, name, x, eps):
    return layernorm(x, p[f"{name}_s"], p[f"{name}_b"], eps)


def _init_enc_layer(r, cfg):
    pt = ParamTree(r, cfg.dtype)
    _ln(pt, "ln1", cfg.d_model)
    pt.sub("attn", A.init_gqa(jax.random.fold_in(r, 1), cfg))
    _ln(pt, "ln2", cfg.d_model)
    pt.sub("mlp", init_gelu_mlp(jax.random.fold_in(r, 2), cfg.d_model,
                                cfg.d_ff, cfg.dtype))
    return pt.build()


def _init_dec_layer(r, cfg):
    pt = ParamTree(r, cfg.dtype)
    _ln(pt, "ln1", cfg.d_model)
    pt.sub("self_attn", A.init_gqa(jax.random.fold_in(r, 1), cfg))
    _ln(pt, "ln2", cfg.d_model)
    pt.sub("cross_attn", A.init_gqa(jax.random.fold_in(r, 2), cfg))
    _ln(pt, "ln3", cfg.d_model)
    pt.sub("mlp", init_gelu_mlp(jax.random.fold_in(r, 3), cfg.d_model,
                                cfg.d_ff, cfg.dtype))
    return pt.build()


def init_encdec(cfg, rng):
    pt = ParamTree(rng, cfg.dtype)
    pt.sub("embed", init_embed(jax.random.fold_in(rng, 0), cfg.vocab_size,
                               cfg.d_model, cfg.dtype, cfg.tie_embeddings))
    pt.sub("enc_layers", stack_inits(lambda r: _init_enc_layer(r, cfg),
                                     jax.random.fold_in(rng, 1),
                                     cfg.encoder_layers))
    pt.sub("dec_layers", stack_inits(lambda r: _init_dec_layer(r, cfg),
                                     jax.random.fold_in(rng, 2),
                                     cfg.num_layers))
    _ln(pt, "enc_norm", cfg.d_model)
    _ln(pt, "dec_norm", cfg.d_model)
    return pt.build()


def encode(params, cfg, frames):
    """frames: (B, T, d) precomputed embeddings (stub frontend)."""
    t = frames.shape[1]
    x = frames + sinusoidal_pos(jnp.arange(t), cfg.d_model)[None].astype(frames.dtype)
    x = shard_act(x, "batch", "seq", "embed")

    def body(xc, lp):
        h, _ = A.gqa_forward(lp["attn"], cfg,
                             _apply_ln(lp, "ln1", xc, cfg.norm_eps),
                             causal=False, use_rope=False,
                             chunk=min(512, t))
        xc = xc + h
        xc = xc + gelu_mlp(lp["mlp"], _apply_ln(lp, "ln2", xc, cfg.norm_eps))
        return xc, None

    if cfg.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return _apply_ln(params, "enc_norm", x, cfg.norm_eps)


def _dec_layer_fwd(lp, cfg, x, enc_out, *, pos_offset=0, chunk=512,
                   collect=False):
    h, kv = A.gqa_forward(lp["self_attn"], cfg,
                          _apply_ln(lp, "ln1", x, cfg.norm_eps),
                          causal=True, use_rope=False, pos_offset=pos_offset,
                          chunk=chunk)
    x = x + h
    h, cross_kv = A.gqa_forward(lp["cross_attn"], cfg,
                                _apply_ln(lp, "ln2", x, cfg.norm_eps),
                                causal=False, use_rope=False,
                                kv_from=enc_out, chunk=chunk)
    x = x + h
    x = x + gelu_mlp(lp["mlp"], _apply_ln(lp, "ln3", x, cfg.norm_eps))
    return x, (kv, cross_kv) if collect else None


def encdec_forward(params, cfg, batch, *, collect_cache=False, chunk=512):
    """batch: {enc_frames, tokens}.  Returns (logits, aux, caches)."""
    enc_out = encode(params, cfg, batch["enc_frames"])
    s = batch["tokens"].shape[1]
    x = embed_tokens(params["embed"], batch["tokens"])
    x = x + sinusoidal_pos(jnp.arange(s), cfg.d_model)[None].astype(x.dtype)

    def body(xc, lp):
        xo, kvs = _dec_layer_fwd(lp, cfg, xc, enc_out, chunk=chunk,
                                 collect=collect_cache)
        return xo, kvs

    if cfg.remat:
        body = jax.checkpoint(body)
    x, kvs = jax.lax.scan(body, x, params["dec_layers"])
    x = _apply_ln(params, "dec_norm", x, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.tie_embeddings)
    zero = jnp.zeros((), jnp.float32)
    return logits, zero, kvs


def encdec_init_cache(cfg, batch_size: int, max_len: int):
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    l, kh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    return {
        "pos": jnp.zeros((), jnp.int32),
        "k": jnp.zeros((l, batch_size, max_len, kh, hd), dt),
        "v": jnp.zeros((l, batch_size, max_len, kh, hd), dt),
        "cross_k": jnp.zeros((l, batch_size, cfg.encoder_seq, kh, hd), dt),
        "cross_v": jnp.zeros((l, batch_size, cfg.encoder_seq, kh, hd), dt),
        "slot_pos": jnp.full((max_len,), -1, jnp.int32),
    }


def encdec_prefill(params, cfg, batch, cache, *, chunk=512):
    s = batch["tokens"].shape[1]
    logits, _, kvs = encdec_forward(params, cfg, batch, collect_cache=True,
                                    chunk=chunk)
    (k, v), (ck, cv) = kvs
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    cache["cross_k"] = ck.astype(cache["cross_k"].dtype)
    cache["cross_v"] = cv.astype(cache["cross_v"].dtype)
    n_slots = cache["slot_pos"].shape[0]
    cache["slot_pos"] = jnp.where(jnp.arange(n_slots) < s,
                                  jnp.arange(n_slots), -1).astype(jnp.int32)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return logits[:, -1:], cache


def encdec_decode_step(params, cfg, cache, tokens):
    pos = cache["pos"]
    x = embed_tokens(params["embed"], tokens)
    x = x + sinusoidal_pos(pos[None], cfg.d_model)[None].astype(x.dtype)
    cache = dict(cache)
    slot = A.cache_slot(cfg, cache["slot_pos"].shape[0], pos)
    slot_pos = jax.lax.dynamic_update_slice(
        cache["slot_pos"], pos[None].astype(jnp.int32), (slot,))
    cache["slot_pos"] = slot_pos

    def body(xc, lin):
        lp, lk, lv, lck, lcv = lin
        h, k, v = A.gqa_decode(lp["self_attn"], cfg,
                               _apply_ln(lp, "ln1", xc, cfg.norm_eps),
                               lk, lv, slot_pos, pos, slot, use_rope=False)
        nk = jax.lax.dynamic_update_slice(lk, k, (0, slot, 0, 0))
        nv = jax.lax.dynamic_update_slice(lv, v, (0, slot, 0, 0))
        xc = xc + h
        h = A.cross_decode(lp["cross_attn"], cfg,
                           _apply_ln(lp, "ln2", xc, cfg.norm_eps), lck, lcv)
        xc = xc + h
        xc = xc + gelu_mlp(lp["mlp"], _apply_ln(lp, "ln3", xc, cfg.norm_eps))
        return xc, (nk, nv)

    x, (nk, nv) = jax.lax.scan(
        body, x, (params["dec_layers"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"]))
    cache.update(k=nk, v=nv, pos=pos + 1)
    x = _apply_ln(params, "dec_norm", x, cfg.norm_eps)
    return unembed(params["embed"], x, cfg.tie_embeddings), cache
