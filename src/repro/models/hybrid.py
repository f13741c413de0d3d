"""Zamba2-style hybrid: Mamba2 layer groups + one SHARED attention+MLP
block applied after every ``cfg.attn_every`` SSM layers.

Adaptation notes (DESIGN.md §4): the reference concatenates the current
hidden state with the original embeddings as the shared block's input
(width 2*d_model) — kept here; the per-application LoRA deltas on the
shared weights are omitted (weights are exactly shared).  The shared
block's weight reuse across 9 applications x many steps is a within-model
instance of the paper's data-reuse premise: its projections are packed
once and hit 9 times per token at decode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import mamba2 as M
from repro.models.layers import init_swiglu, rmsnorm, swiglu
from repro.models.param import ParamTree, stack_inits
from repro.sharding.context import shard_act


def _n_groups(cfg):
    assert cfg.num_layers % cfg.attn_every == 0
    return cfg.num_layers // cfg.attn_every


def init_hybrid(cfg, rng):
    from repro.models.layers import init_embed
    pt = ParamTree(rng, cfg.dtype)
    pt.sub("embed", init_embed(jax.random.fold_in(rng, 0), cfg.vocab_size,
                               cfg.d_model, cfg.dtype, cfg.tie_embeddings))

    def one_mamba(r):
        lpt = ParamTree(r, cfg.dtype)
        lpt.ones("ln1", (cfg.d_model,), ("embed",))
        lpt.sub("mamba", M.init_mamba2(jax.random.fold_in(r, 1), cfg))
        return lpt.build()

    ng = _n_groups(cfg)
    stacked, axes = stack_inits(one_mamba, jax.random.fold_in(rng, 1),
                                cfg.num_layers)
    # reshape (L, ...) -> (groups, per_group, ...) for the nested scan
    stacked = jax.tree.map(
        lambda v: v.reshape(ng, cfg.attn_every, *v.shape[1:]), stacked)
    axes = jax.tree.map(lambda a: ("groups",) + a, axes,
                        is_leaf=lambda x: isinstance(x, tuple)
                        and all(isinstance(s, (str, type(None))) for s in x))
    pt._params["mamba_layers"] = stacked
    pt._axes["mamba_layers"] = axes

    # the shared transformer block (input = concat(x, x0): width 2d)
    sb = ParamTree(jax.random.fold_in(rng, 2), cfg.dtype)
    sb.ones("ln1", (2 * cfg.d_model,), ("embed",))
    sb.sub("attn", A.init_gqa(jax.random.fold_in(rng, 3), cfg,
                              d_in=2 * cfg.d_model))
    sb.ones("ln2", (2 * cfg.d_model,), ("embed",))
    sb.sub("mlp", init_swiglu(jax.random.fold_in(rng, 4), 2 * cfg.d_model,
                              cfg.d_ff, cfg.dtype, d_out=cfg.d_model))
    pt.sub("shared", sb.build())
    pt.ones("final_norm", (cfg.d_model,), ("embed",))
    return pt.build()


def _shared_fwd(p, cfg, x, x0, *, pos_offset=0, chunk=512):
    h = rmsnorm(jnp.concatenate([x, x0], axis=-1), p["ln1"], cfg.norm_eps)
    a, kv = A.gqa_forward(p["attn"], cfg, h, pos_offset=pos_offset, chunk=chunk)
    x = x + a
    h = rmsnorm(jnp.concatenate([x, x0], axis=-1), p["ln2"], cfg.norm_eps)
    return x + swiglu(p["mlp"], h), kv


def _shared_decode(p, cfg, x, x0, ck, cv, slot_pos, pos, slot):
    h = rmsnorm(jnp.concatenate([x, x0], axis=-1), p["ln1"], cfg.norm_eps)
    a, k, v = A.gqa_decode(p["attn"], cfg, h, ck, cv, slot_pos, pos, slot)
    ck = jax.lax.dynamic_update_slice(ck, k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v, (0, slot, 0, 0))
    x = x + a
    h = rmsnorm(jnp.concatenate([x, x0], axis=-1), p["ln2"], cfg.norm_eps)
    return x + swiglu(p["mlp"], h), ck, cv


def hybrid_forward(params, cfg, batch, *, collect_cache=False, chunk=512):
    from repro.models.layers import embed_tokens, unembed
    x = embed_tokens(params["embed"], batch["tokens"])
    x = shard_act(x, "batch", "seq", "embed")
    x0 = x

    def mamba_body(xc, lp):
        h, (ssm, conv) = M.mamba2_forward(
            lp["mamba"], cfg, rmsnorm(xc, lp["ln1"], cfg.norm_eps))
        return xc + h, (ssm, conv) if collect_cache else None

    if cfg.remat:
        mamba_body = jax.checkpoint(mamba_body)

    def group_body(xc, glp):
        xc, states = jax.lax.scan(mamba_body, xc, glp)
        xc, kv = _shared_fwd(params["shared"], cfg, xc, x0, chunk=chunk)
        return xc, (states, kv if collect_cache else None)

    x, (states, kvs) = jax.lax.scan(group_body, x, params["mamba_layers"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.tie_embeddings)
    zero = jnp.zeros((), jnp.float32)
    return logits, zero, ((states, kvs) if collect_cache else (None, None))


def hybrid_init_cache(cfg, batch_size: int, max_len: int):
    ng = _n_groups(cfg)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    di, h, p_, n, g = (cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state, cfg.ssm_groups)
    return {
        "pos": jnp.zeros((), jnp.int32),
        "ssm": jnp.zeros((ng, cfg.attn_every, batch_size, h, p_, n), jnp.float32),
        "conv": jnp.zeros((ng, cfg.attn_every, batch_size, cfg.ssm_conv - 1,
                           di + 2 * g * n), dt),
        "k": jnp.zeros((ng, batch_size, max_len, cfg.num_kv_heads,
                        cfg.head_dim), dt),
        "v": jnp.zeros((ng, batch_size, max_len, cfg.num_kv_heads,
                        cfg.head_dim), dt),
        "slot_pos": jnp.full((max_len,), -1, jnp.int32),
    }


def hybrid_prefill(params, cfg, batch, cache, *, chunk=512):
    s = batch["tokens"].shape[1]
    logits, _, (states, kvs) = hybrid_forward(params, cfg, batch,
                                              collect_cache=True, chunk=chunk)
    ssm, conv = states
    ka, kv_ = kvs
    cache = dict(cache)
    cache["ssm"], cache["conv"] = ssm, conv.astype(cache["conv"].dtype)
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], ka.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], kv_.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    n_slots = cache["slot_pos"].shape[0]
    cache["slot_pos"] = jnp.where(jnp.arange(n_slots) < s,
                                  jnp.arange(n_slots), -1).astype(jnp.int32)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return logits[:, -1:], cache


def hybrid_decode_step(params, cfg, cache, tokens):
    from repro.models.layers import embed_tokens, unembed
    pos = cache["pos"]
    x = embed_tokens(params["embed"], tokens)
    x0 = x
    cache = dict(cache)
    slot = A.cache_slot(cfg, cache["slot_pos"].shape[0], pos)
    slot_pos = jax.lax.dynamic_update_slice(
        cache["slot_pos"], pos[None].astype(jnp.int32), (slot,))
    cache["slot_pos"] = slot_pos

    def mamba_body(xc, lin):
        lp, ls, lc = lin
        h, ssm, conv = M.mamba2_decode(
            lp["mamba"], cfg, rmsnorm(xc, lp["ln1"], cfg.norm_eps), ls, lc, pos)
        return xc + h, (ssm, conv)

    def group_body(xc, gin):
        glp, gssm, gconv, gk, gv = gin
        xc, (ssm, conv) = jax.lax.scan(mamba_body, xc, (glp, gssm, gconv))
        xc, ck, cv = _shared_decode(params["shared"], cfg, xc, x0, gk, gv,
                                    slot_pos, pos, slot)
        return xc, (ssm, conv, ck, cv)

    x, (ssm, conv, k, v) = jax.lax.scan(
        group_body, x,
        (params["mamba_layers"], cache["ssm"], cache["conv"], cache["k"],
         cache["v"]))
    cache.update(ssm=ssm, conv=conv, k=k, v=v, pos=pos + 1)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params["embed"], x, cfg.tie_embeddings), cache
