"""LM assembly: dense / MoE / SSM / VLM stacks with layer-scan.

One scanned homogeneous block stack (+ optional unscanned leading dense
layers for deepseek-style ``first_k_dense``), pre-norm residual blocks,
tied or separate unembedding.  ``jax.checkpoint`` wraps the scan body when
``cfg.remat`` (full-recompute policy by default; the §Perf hillclimb
explores ``dots_saveable``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.packing import is_packed
from repro.models import attention as A
from repro.models import mamba2 as M
from repro.models import moe as MOE
from repro.models.layers import (embed_tokens, init_embed, init_swiglu,
                                 rmsnorm, swiglu, unembed)
from repro.models.param import ParamTree, stack_inits
from repro.sharding.context import shard_act


# ---------------------------------------------------------------------------
# per-layer init/forward/decode
# ---------------------------------------------------------------------------


def _init_layer(rng, cfg, kind: str):
    """kind: 'dense' | 'moe' | 'ssm'."""
    pt = ParamTree(rng, cfg.dtype)
    if kind == "ssm":
        pt.ones("ln1", (cfg.d_model,), ("embed",))
        pt.sub("mamba", M.init_mamba2(jax.random.fold_in(rng, 1), cfg))
        return pt.build()
    pt.ones("ln1", (cfg.d_model,), ("embed",))
    if cfg.use_mla:
        pt.sub("attn", A.init_mla(jax.random.fold_in(rng, 1), cfg))
    else:
        pt.sub("attn", A.init_gqa(jax.random.fold_in(rng, 1), cfg))
    pt.ones("ln2", (cfg.d_model,), ("embed",))
    if kind == "moe":
        pt.sub("mlp", MOE.init_moe(jax.random.fold_in(rng, 2), cfg))
    else:
        pt.sub("mlp", init_swiglu(jax.random.fold_in(rng, 2), cfg.d_model,
                                  cfg.d_ff, cfg.dtype))
    return pt.build()


def _layer_fwd(p, cfg, x, kind: str, *, pos_offset=0, chunk=512,
               valid_from=None):
    """Returns (x, kv_for_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "ssm":
        h, (ssm, conv) = M.mamba2_forward(p["mamba"], cfg,
                                          rmsnorm(x, p["ln1"], cfg.norm_eps))
        return x + h, (ssm, conv), aux
    hin = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        h, kv = A.mla_forward(p["attn"], cfg, hin, pos_offset=pos_offset,
                              chunk=chunk, valid_from=valid_from)
    else:
        h, kv = A.gqa_forward(p["attn"], cfg, hin, pos_offset=pos_offset,
                              chunk=chunk, valid_from=valid_from)
    x = x + h
    hin = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if kind == "moe":
        h, aux = MOE.moe_apply(p["mlp"], cfg, hin)
    else:
        h = swiglu(p["mlp"], hin)
    return x + h, kv, aux


def _layer_decode(p, cfg, x, lcache, slot_pos, pos, slot, kind: str,
                  valid_from=None):
    """One-token step through one layer.  Returns (x, new): the new SSM
    state for ``ssm``, else the token's two cache entries (B,1,...) for
    the caller to write into ``lcache``'s ``slot``, which the layer
    masks; ``lcache`` is read only."""
    if kind == "ssm":
        h, ssm, conv = M.mamba2_decode(p["mamba"], cfg,
                                       rmsnorm(x, p["ln1"], cfg.norm_eps),
                                       lcache[0], lcache[1], pos)
        return x + h, (ssm, conv)
    hin = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        h, c, kr = A.mla_decode(p["attn"], cfg, hin, lcache[0], lcache[1],
                                pos, slot,
                                valid_from=valid_from)
        new = (c, kr)
    else:
        h, k, v = A.gqa_decode(p["attn"], cfg, hin, lcache[0], lcache[1],
                               slot_pos, pos, slot, valid_from=valid_from)
        new = (k, v)
    x = x + h
    hin = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if kind == "moe":
        h, _ = MOE.moe_apply(p["mlp"], cfg, hin)
    else:
        h = swiglu(p["mlp"], hin)
    return x + h, new


def _kind(cfg) -> str:
    if cfg.family == "ssm":
        return "ssm"
    if cfg.family == "moe":
        return "moe"
    return "dense"  # dense / vlm share the block


# ---------------------------------------------------------------------------
# the ONE layer-stack traversal
# ---------------------------------------------------------------------------


@jax.named_scope("layers")
def layer_stack(cfg, x, layer_params, step, extras=(), *, remat=None,
                scan=None):
    """THE layer-stack entry point: every full-stack traversal (training /
    prefill forward AND both decode cache branches) lowers through this one
    helper, so all compiled programs share a single scan-body shape the
    ProgramStore can fingerprint (DESIGN.md §13).

    ``step(lp, x, *extra_slices) -> (x, per_layer_out)`` is the per-layer
    body; ``extras`` are layer-stacked arrays scanned alongside the params
    (SSM state, which each decode step rewrites whole).  Decode passes
    the layer index as ``layer_params`` and reads the params and its
    cache in place (:func:`lm_decode_step`).  ``remat``/``scan`` default
    to the config flags (forward);
    decode passes ``remat=False, scan=True`` explicitly — a one-token
    step never recomputes and always scans.
    """
    remat = cfg.remat if remat is None else remat
    scan = cfg.scan_layers if scan is None else scan
    xs = (layer_params,) + tuple(extras)

    # the body's own ops under ``layer``; the scan's slicing of ``xs`` and
    # stacking of outputs stay outside it
    @jax.named_scope("layer")
    def body(xc, sl):
        return step(sl[0], xc, *sl[1:])

    if remat:
        body = jax.checkpoint(body)
    if scan:
        return jax.lax.scan(body, x, xs)
    n = jax.tree.leaves(layer_params)[0].shape[0]
    outs = []
    for i in range(n):
        sl = jax.tree.map(lambda v: v[i], xs)
        x, out = body(x, sl)
        outs.append(out)
    stacked = jax.tree.map(lambda *vs: jnp.stack(vs), *outs)
    return x, stacked


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------


def init_lm(cfg, rng):
    kind = _kind(cfg)
    pt = ParamTree(rng, cfg.dtype)
    pt.sub("embed", init_embed(jax.random.fold_in(rng, 0), cfg.vocab_size,
                               cfg.d_model, cfg.dtype, cfg.tie_embeddings))
    n_scan = cfg.num_layers - cfg.first_k_dense
    for i in range(cfg.first_k_dense):
        pt.sub(f"dense{i}", _init_layer(jax.random.fold_in(rng, 1000 + i),
                                        cfg, "dense"))
    pt.sub("layers", stack_inits(
        lambda r: _init_layer(r, cfg, kind), jax.random.fold_in(rng, 1), n_scan))
    pt.ones("final_norm", (cfg.d_model,), ("embed",))
    return pt.build()


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def _inputs_to_h(params, cfg, batch):
    """tokens (+ vlm embeds) -> first hidden states."""
    if cfg.embeds_input:
        tok = embed_tokens(params["embed"], batch["tokens"])
        x = jnp.concatenate([batch["embeds"].astype(tok.dtype), tok], axis=1)
    else:
        x = embed_tokens(params["embed"], batch["tokens"])
    return shard_act(x, "batch", "seq", "embed")


def lm_forward(params, cfg, batch, *, collect_cache: bool = False,
               pos_offset=0, chunk: int = 512):
    """Returns (logits f32, aux_loss, kv_stack | None).

    ``batch["pad"]`` (optional, (B,) int32): per-row count of left-pad
    tokens — ragged-prompt admission pads each prompt to a length bucket
    on the LEFT and masks the pad positions out of attention, keeping the
    batch position-aligned for lockstep decode (DESIGN.md §8)."""
    kind = _kind(cfg)
    x = _inputs_to_h(params, cfg, batch)
    valid_from = None
    if batch.get("pad") is not None:
        # absolute mask boundary: row r's real tokens start at offset+pad[r]
        valid_from = pos_offset + batch["pad"].astype(jnp.int32)

    aux_total = jnp.zeros((), jnp.float32)
    dense_kvs = {}
    for i in range(cfg.first_k_dense):
        x, kv, aux = _layer_fwd(params[f"dense{i}"], cfg, x, "dense",
                                pos_offset=pos_offset, chunk=chunk,
                                valid_from=valid_from)
        if collect_cache:
            dense_kvs[i] = kv
        aux_total = aux_total + aux

    def step(lp, xc):
        xo, kv, aux = _layer_fwd(lp, cfg, xc, kind, pos_offset=pos_offset,
                                 chunk=chunk, valid_from=valid_from)
        return xo, (kv if collect_cache else None, aux)

    x, (kvs, auxs) = layer_stack(cfg, x, params["layers"], step)
    aux_total = aux_total + auxs.sum()

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.tie_embeddings)
    return logits, aux_total, (kvs, dense_kvs) if collect_cache else (None, None)


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------


def init_cache(cfg, batch_size: int, max_len: int):
    """Zeroed decode cache (also the dry-run ShapeDtypeStruct template)."""
    kind = _kind(cfg)
    n_scan = cfg.num_layers - cfg.first_k_dense
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    cache: dict = {"pos": jnp.zeros((), jnp.int32)}
    if kind == "ssm":
        di, h, p_, n, g = (cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim,
                           cfg.ssm_state, cfg.ssm_groups)
        cache["ssm"] = jnp.zeros((n_scan, batch_size, h, p_, n), jnp.float32)
        cache["conv"] = jnp.zeros(
            (n_scan, batch_size, cfg.ssm_conv - 1, di + 2 * g * n), dt)
        return cache
    slots = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    cache["slot_pos"] = jnp.full((slots,), -1, jnp.int32)
    # per-row admission boundary: cache positions < valid_from[r] are
    # left-padding or a recycled slot's dead stream (DESIGN.md §8)
    cache["valid_from"] = jnp.zeros((batch_size,), jnp.int32)
    if cfg.use_mla:
        cache["c"] = jnp.zeros((n_scan, batch_size, slots, cfg.kv_lora_rank), dt)
        cache["kr"] = jnp.zeros((n_scan, batch_size, slots, cfg.rope_head_dim), dt)
    else:
        kh, hd = cfg.num_kv_heads, cfg.head_dim
        cache["k"] = jnp.zeros((n_scan, batch_size, slots, kh, hd), dt)
        cache["v"] = jnp.zeros((n_scan, batch_size, slots, kh, hd), dt)
    for i in range(cfg.first_k_dense):
        kh, hd = cfg.num_kv_heads, cfg.head_dim
        if cfg.use_mla:
            cache[f"dense{i}_c"] = jnp.zeros((batch_size, slots, cfg.kv_lora_rank), dt)
            cache[f"dense{i}_kr"] = jnp.zeros((batch_size, slots, cfg.rope_head_dim), dt)
        else:
            cache[f"dense{i}_k"] = jnp.zeros((batch_size, slots, kh, hd), dt)
            cache[f"dense{i}_v"] = jnp.zeros((batch_size, slots, kh, hd), dt)
    return cache


def _cache_pair_names(cfg):
    return ("c", "kr") if cfg.use_mla else ("k", "v")


def lm_prefill(params, cfg, batch, cache, *, chunk: int = 512):
    """Run the full prompt, fill the cache.  Returns (last_logits, cache)."""
    kind = _kind(cfg)
    s = (batch["tokens"].shape[1] + (batch["embeds"].shape[1]
                                     if cfg.embeds_input else 0))
    logits, _, (kvs, dense_kvs) = lm_forward(params, cfg, batch,
                                             collect_cache=True, chunk=chunk)
    cache = dict(cache)
    if "valid_from" in cache:
        pad = batch.get("pad")
        b = batch["tokens"].shape[0]
        cache["valid_from"] = (pad.astype(jnp.int32) if pad is not None
                               else jnp.zeros((b,), jnp.int32))
    if kind == "ssm":
        cache["ssm"], cache["conv"] = kvs
        cache["pos"] = jnp.asarray(s, jnp.int32)
        return logits[:, -1:], cache
    a, b_ = _cache_pair_names(cfg)
    for i, (da, db) in dense_kvs.items():
        cache[f"dense{i}_{a}"] = jax.lax.dynamic_update_slice(
            cache[f"dense{i}_{a}"], da.astype(cache[f"dense{i}_{a}"].dtype),
            (0, 0) + (0,) * (da.ndim - 2))
        cache[f"dense{i}_{b_}"] = jax.lax.dynamic_update_slice(
            cache[f"dense{i}_{b_}"], db.astype(cache[f"dense{i}_{b_}"].dtype),
            (0, 0) + (0,) * (db.ndim - 2))
    ka, kb = kvs
    slots = cache[a].shape[2]
    if cfg.sliding_window and s > slots:
        # keep the last `slots` positions, rolled so slot = pos % slots
        ka, kb = ka[:, :, -slots:], kb[:, :, -slots:]
        start = s - slots
        idx = (start + jnp.arange(slots)) % slots
        inv = jnp.argsort(idx)
        ka, kb = ka[:, :, inv], kb[:, :, inv]
        cache["slot_pos"] = (start + jnp.arange(slots))[inv]
        cache[a] = ka.astype(cache[a].dtype)
        cache[b_] = kb.astype(cache[b_].dtype)
    else:
        cache[a] = jax.lax.dynamic_update_slice(
            cache[a], ka.astype(cache[a].dtype), (0, 0, 0) + (0,) * (cache[a].ndim - 3))
        cache[b_] = jax.lax.dynamic_update_slice(
            cache[b_], kb.astype(cache[b_].dtype), (0, 0, 0) + (0,) * (cache[b_].ndim - 3))
        cache["slot_pos"] = jnp.where(jnp.arange(cache["slot_pos"].shape[0]) < s,
                                      jnp.arange(cache["slot_pos"].shape[0]),
                                      -1).astype(jnp.int32)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return logits[:, -1:], cache


def _layer_view(layers, i):
    """Layer ``i`` of the stacked layer params, read in place: a packed
    weight becomes the view its kernel indexes (``PackedTensor.at_layer``);
    any other leaf is indexed here."""
    return jax.tree.map(lambda v: v.at_layer(i) if is_packed(v) else v[i],
                        layers, is_leaf=is_packed)


def lm_decode_step(params, cfg, cache, tokens):
    """tokens (B,1) -> (logits (B,1,V) f32, updated cache).

    The layer scan walks the layer index alone.  The stacked params and a
    positional cache (K/V, or MLA's c/kr) are loop invariants read in
    place: a packed weight by its kernel at row ``i``, the cache slab as
    ``cache[i]``.  The scan returns only the token's cache entries
    (L,B,1,...); one write per cache tensor lands after it.  A scan
    output cannot alias its input, so a cache passed as ``xs``/``ys``
    would be sliced, restacked and copied whole on every step, and a
    weight passed as ``xs`` is sliced out ahead of its kernel.  SSM state
    is rewritten whole each step and stays scanned."""
    kind = _kind(cfg)
    pos = cache["pos"]
    x = embed_tokens(params["embed"], tokens)
    cache = dict(cache)
    layers = params["layers"]
    idx = jnp.arange(jax.tree.leaves(layers)[0].shape[0])

    if kind == "ssm":
        def step(i, xc, ssm, conv):
            return _layer_decode(_layer_view(layers, i), cfg, xc,
                                 (ssm, conv), None, pos, None, kind)

        x, (cache["ssm"], cache["conv"]) = layer_stack(
            cfg, x, idx, step, (cache["ssm"], cache["conv"]),
            remat=False, scan=True)
    else:
        slot_pos, valid_from = cache["slot_pos"], cache.get("valid_from")
        slot = A.cache_slot(cfg, slot_pos.shape[0], pos)
        a, b_ = _cache_pair_names(cfg)
        new = {}
        for i in range(cfg.first_k_dense):
            da, db = f"dense{i}_{a}", f"dense{i}_{b_}"
            x, (new[da], new[db]) = _layer_decode(
                params[f"dense{i}"], cfg, x, (cache[da], cache[db]), slot_pos,
                pos, slot, "dense", valid_from=valid_from)
        ca, cb = cache[a], cache[b_]

        def step(i, xc):
            return _layer_decode(_layer_view(layers, i), cfg, xc,
                                 (ca[i], cb[i]), slot_pos, pos, slot, kind,
                                 valid_from=valid_from)

        x, (new[a], new[b_]) = layer_stack(cfg, x, idx, step, remat=False,
                                           scan=True)
        with jax.named_scope("cache_write"):
            for name, entry in new.items():
                lead = (0, 0) if name in (a, b_) else (0,)   # (layer,) batch
                start = lead + (slot,) + (0,) * (entry.ndim - len(lead) - 1)
                cache[name] = jax.lax.dynamic_update_slice(
                    cache[name], entry.astype(cache[name].dtype), start)
            cache["slot_pos"] = jax.lax.dynamic_update_slice(
                slot_pos, pos[None].astype(jnp.int32), (slot,))

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.tie_embeddings)
    cache["pos"] = pos + 1
    return logits, cache


def lm_prefill_row(params, cfg, batch, cache, row, t_end):
    """Ragged admission (DESIGN.md §8): prefill ONE request into row
    ``row`` of a LIVE decode cache without disturbing the other streams.

    ``batch`` has leading dim 1, its prompt left-padded to a length
    bucket ``lb`` (``batch["pad"]``: (1,) pad count).  The prompt
    occupies absolute positions ``[t_end - lb, t_end)`` — RoPE attention
    is relative, so a stream shifted to the scheduler's clock decodes
    identically to one placed at position 0 — and ``valid_from[row]``
    masks the pad region plus whatever a previous stream left in the
    recycled slot.  ``row``/``t_end`` may be traced: ONE compiled program
    per length bucket serves every slot and clock value.

    Returns (last_logits (1,1,V), cache); the caller owns the clock
    (``cache["pos"]`` is not touched).
    """
    kind = _kind(cfg)
    if kind == "ssm":
        raise NotImplementedError(
            "ragged admission needs an attention cache; SSM state is "
            "order-dependent and cannot mask left-padding")
    if cfg.sliding_window:
        raise NotImplementedError(
            "ragged admission into a rolling sliding-window cache is not "
            "supported (slot != absolute position)")
    lb = batch["tokens"].shape[1] + (batch["embeds"].shape[1]
                                     if cfg.embeds_input else 0)
    row = jnp.asarray(row, jnp.int32)
    t0 = jnp.asarray(t_end, jnp.int32) - lb
    logits, _, (kvs, dense_kvs) = lm_forward(params, cfg, batch,
                                             collect_cache=True,
                                             pos_offset=t0)
    return logits[:, -1:], _write_row(cfg, cache, kvs, dense_kvs, batch,
                                      row, t0, lb)


@jax.named_scope("cache_write")
def _write_row(cfg, cache, kvs, dense_kvs, batch, row, t0, lb):
    """``lm_prefill_row``'s cache write: the prompt's K/V into row ``row``
    at slots ``[t0, t0 + lb)``, its ``valid_from`` and the occupied
    slots."""
    cache = dict(cache)
    a, b_ = _cache_pair_names(cfg)
    ka, kb = kvs
    # kvs: (n_scan, 1, lb, ...) -> this row's slots [t0, t_end)
    cache[a] = jax.lax.dynamic_update_slice(
        cache[a], ka.astype(cache[a].dtype),
        (0, row, t0) + (0,) * (cache[a].ndim - 3))
    cache[b_] = jax.lax.dynamic_update_slice(
        cache[b_], kb.astype(cache[b_].dtype),
        (0, row, t0) + (0,) * (cache[b_].ndim - 3))
    for i, (da, db) in dense_kvs.items():
        cache[f"dense{i}_{a}"] = jax.lax.dynamic_update_slice(
            cache[f"dense{i}_{a}"], da.astype(cache[f"dense{i}_{a}"].dtype),
            (row, t0) + (0,) * (da.ndim - 2))
        cache[f"dense{i}_{b_}"] = jax.lax.dynamic_update_slice(
            cache[f"dense{i}_{b_}"], db.astype(cache[f"dense{i}_{b_}"].dtype),
            (row, t0) + (0,) * (db.ndim - 2))
    pad = batch.get("pad")
    vf = t0 + (pad.astype(jnp.int32)[0] if pad is not None else 0)
    cache["valid_from"] = jax.lax.dynamic_update_slice(
        cache["valid_from"], vf[None], (row,))
    # mark the occupied slots in the shared slot->position map (idempotent:
    # slot == absolute position when there is no sliding window)
    sl = jnp.arange(cache["slot_pos"].shape[0], dtype=jnp.int32)
    cache["slot_pos"] = jnp.where((sl >= t0) & (sl < t0 + lb), sl,
                                  cache["slot_pos"]).astype(jnp.int32)
    return cache
