"""Attention: chunked (flash-style) GQA for train/prefill, cache-based
decode, sliding-window variants, and MLA (DeepSeek-V2) with the absorbed
decode formulation over the compressed KV cache.

The train/prefill path scans over query and key chunks with online softmax
so peak memory is O(chunk^2), never O(S^2) — required for the 32k prefill
cells to fit.  Decode (one token against a cache) is a single masked
einsum: O(S) — this is the TSMM-shaped regime the paper's technique
serves (skinny activations against wide projection weights).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.linear import in_serving_ctx, linear
from repro.models.layers import (apply_rope, rmsnorm, rope_tables,
                                 yarn_mscale, yarn_of)
from repro.models.param import ParamTree
from repro.sharding.context import get_ctx, shard_act

NEG_INF = -1e30


def _divisor_chunk(s: int, chunk: int) -> int:
    """Largest chunk <= `chunk` that divides s (1500 -> 500 for whisper)."""
    c = min(chunk, s)
    while s % c:
        c -= 1
    return c


# ---------------------------------------------------------------------------
# chunked causal attention (train / prefill)
# ---------------------------------------------------------------------------


def _chunk_body(q, k, v, q_pos, k_pos, scale, window, causal, valid_from=None):
    """One (q-chunk x k-chunk) tile.  q: (B,Cq,KH,G,D) k/v: (B,Ck,KH,D).

    ``valid_from``: optional (B,) absolute position of each row's first
    real token — keys before it are left-padding and masked out (ragged-
    prompt admission, DESIGN.md §8)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    if valid_from is None:
        return jnp.where(mask[None, None, None], s, NEG_INF)
    mask = mask[None] & (k_pos[None, None, :] >= valid_from[:, None, None])
    return jnp.where(mask[:, None, None], s, NEG_INF)


def _flash(q, k, v, causal: bool):
    """The Pallas flash kernel on (B, S, H, D) operands; GQA KV heads are
    repeated up to H first."""
    from repro.kernels.flash_attention import flash_attention
    g = q.shape[2] // k.shape[2]
    kr = jnp.repeat(k, g, axis=2) if g > 1 else k
    vr = jnp.repeat(v, g, axis=2) if g > 1 else v
    out = flash_attention(q.transpose(0, 2, 1, 3), kr.transpose(0, 2, 1, 3),
                          vr.transpose(0, 2, 1, 3), causal=causal)
    return out.transpose(0, 2, 1, 3)


def _flash_per_shard(q, k, v, causal: bool):
    """:func:`_flash` under the ambient mesh, one kernel per shard (XLA
    cannot partition a Mosaic kernel).  None when the query and KV heads
    are not sharded alike: a shard would then pair its query heads with
    the wrong KV group."""
    ctx = get_ctx()
    if ctx is None:
        return _flash(q, k, v, causal)
    qs = ctx.spec_for(("batch", None, "heads", None), q.shape)
    ks = ctx.spec_for(("batch", None, "kvheads", None), k.shape)
    if qs != ks:
        return None
    return jax.shard_map(functools.partial(_flash, causal=causal),
                         mesh=ctx.mesh, in_specs=(qs, ks, ks), out_specs=qs,
                         check_vma=False)(q, k, v)


@jax.named_scope("attention")
def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      chunk: int = 512, q_offset=0, k_offset=None,
                      valid_from=None, scale=None):
    """q: (B,Sq,H,D)  k,v: (B,Sk,KH,D).  Returns (B,Sq,H,D).

    Online-softmax double scan: outer over q chunks (sequential, O(1)
    extra memory), inner over k chunks (carries m/l/acc).

    ``k_offset`` defaults to ``q_offset`` (aligned self-attention: both
    operands carry the same absolute positions, so an offset stream —
    ragged admission at a nonzero clock — keeps a correct causal mask);
    pass ``k_offset=0`` for cross-attention keys that start at 0.
    ``valid_from``: (B,) absolute first-real-token position per row
    (left-pad masking); ``q_offset`` may be traced under jit.  ``scale``
    multiplies the scores (default ``D ** -0.5``).

    On TPU, full-window self-attention traced for serving
    (``core.linear.serving_ctx``) dispatches to the fused Pallas flash
    kernel (kernels/flash_attention.py): scores stay in VMEM and
    above-diagonal blocks are skipped.  The kernel has no differentiation
    rule, so training stays on the jnp path below, which is also the CPU /
    SWA / cross-attention / ragged path and the kernel's oracle.
    """
    if k_offset is None:
        k_offset = q_offset
    if (in_serving_ctx() and jax.default_backend() == "tpu" and window == 0
            and scale is None and v.shape[-1] == q.shape[-1]
            and isinstance(q_offset, int) and q_offset == 0
            and isinstance(k_offset, int) and k_offset == 0
            and valid_from is None
            and q.shape[1] == k.shape[1] and q.shape[1] % 256 == 0):
        out = _flash_per_shard(q, k, v, causal)
        if out is not None:
            return out
    b, sq, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    dv = v.shape[-1]          # may differ from d (MLA: dk=nope+rope, dv=v)
    g = h // kh
    scale = d ** -0.5 if scale is None else scale
    cq = _divisor_chunk(sq, chunk)
    ck = _divisor_chunk(sk, chunk)
    nq, nk = sq // cq, sk // ck

    qg = q.reshape(b, nq, cq, kh, g, d)
    kc = k.reshape(b, nk, ck, kh, d)
    vc = v.reshape(b, nk, ck, kh, dv)

    def q_step(_, qi):
        qc, qpos = qi

        def k_step(carry, ki):
            m, l, acc = carry
            kb, vb, kpos = ki
            s = _chunk_body(qc, kb, vb, qpos, kpos, scale, window, causal,
                            valid_from)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb, preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((b, kh, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, cq), jnp.float32)
        a0 = jnp.zeros((b, kh, g, cq, dv), jnp.float32)
        kpos_all = (k_offset + jnp.arange(nk * ck)).reshape(nk, ck)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0),
            (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), kpos_all))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(q.dtype)

    qpos_all = (q_offset + jnp.arange(nq * cq)).reshape(nq, cq)
    _, outs = jax.lax.scan(q_step, None,
                           (qg.transpose(1, 0, 2, 3, 4, 5), qpos_all))
    # outs: (nq, b, kh, g, cq, dv) -> (b, sq, h, dv)
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, dv)


@jax.named_scope("attention")
def decode_attention(q, k_cache, v_cache, k_pos, cur_pos, *, window: int = 0,
                     valid_from=None, k_new=None, v_new=None):
    """One-step attention.  q: (B,1,H,D); caches: (B,S,KH,D);
    k_pos: (S,) absolute positions held by each cache slot (-1 = empty);
    valid_from: optional (B,) per-row first-valid position — slots before
    it belong to left-padding or a previous (recycled) stream.

    ``k_new``/``v_new`` (B,1,KH,D): the step's own key and value, not in
    the cache.  Their score joins the cached slots' in the one softmax,
    which equals writing them into their slot and attending over the
    cache; ``k_pos`` must then mark that slot empty."""
    b, _, h, d = q.shape
    sk, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    qg = q.reshape(b, kh, g, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * d ** -0.5
    valid = (k_pos >= 0) & (k_pos <= cur_pos)
    if window:
        valid &= cur_pos - k_pos < window
    if valid_from is not None:
        s = jnp.where((valid[None, :] &
                       (k_pos[None, :] >= valid_from[:, None]))[:, None, None],
                      s, NEG_INF)
    else:
        s = jnp.where(valid[None, None, None], s, NEG_INF)
    if k_new is not None:
        s_new = jnp.einsum("bhgd,bhd->bhg", qg, k_new[:, 0],
                           preferred_element_type=jnp.float32) * d ** -0.5
        s = jnp.concatenate([s, s_new[..., None]], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p[..., :sk], v_cache,
                     preferred_element_type=jnp.float32)
    if v_new is not None:
        out = out + p[..., sk:] * v_new[:, 0, :, None, :]
    return out.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


def init_gqa(rng, cfg, d_in: int = 0, d_out: int = 0):
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    d_in = d_in or d
    pt = ParamTree(rng, cfg.dtype)
    pt.dense("wq", (d_in, h * hd), ("embed", "qheads"))
    pt.dense("wk", (d_in, kh * hd), ("embed", "kvheads"))
    pt.dense("wv", (d_in, kh * hd), ("embed", "kvheads"))
    pt.dense("wo", (h * hd, d_out or d), ("qheads", "embed"))
    if cfg.qkv_bias:
        pt.zeros("bq", (h * hd,), ("qheads",))
        pt.zeros("bk", (kh * hd,), ("kvheads",))
        pt.zeros("bv", (kh * hd,), ("kvheads",))
    return pt.build()


def _qkv(p, cfg, x, kv_from=None):
    b, s, _ = x.shape
    src = x if kv_from is None else kv_from
    sk = src.shape[1]
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear(x, p["wq"], p.get("bq"), name="wq").reshape(b, s, h, hd)
    k = linear(src, p["wk"], p.get("bk"), name="wk").reshape(b, sk, kh, hd)
    v = linear(src, p["wv"], p.get("bv"), name="wv").reshape(b, sk, kh, hd)
    return q, k, v


def gqa_forward(p, cfg, x, *, causal=True, pos_offset=0,
                chunk: int = 512, use_rope: bool = True, kv_from=None,
                valid_from=None):
    """Full-sequence attention (train / prefill).  Returns (out, (k, v)).
    ``kv_from``: cross-attention source sequence (whisper decoder).
    ``valid_from``: (B,) absolute left-pad boundary per row (ragged
    admission); ``pos_offset`` may be traced (admission at a clock)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x, kv_from=kv_from)
    pos = pos_offset + jnp.arange(s)
    if use_rope:
        cos, sin = rope_tables(pos, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = shard_act(q, "batch", "seq", "heads", None)
    k = shard_act(k, "batch", "seq", "kvheads", None)
    v = shard_act(v, "batch", "seq", "kvheads", None)
    out = chunked_attention(q, k, v, causal=causal,
                            window=cfg.sliding_window, chunk=chunk,
                            q_offset=pos_offset,
                            k_offset=0 if kv_from is not None else None,
                            valid_from=valid_from)
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    return linear(out, p["wo"], name="wo"), (k, v)


def cache_slot(cfg, slots: int, pos):
    """The cache slot the token at ``pos`` is written to: ``pos % slots``
    in a sliding-window ring, else ``pos``.  A decode step masks this slot
    (:func:`gqa_decode`, :func:`mla_decode`) and its caller writes the
    token's entries there, both from this one expression."""
    pos = jnp.asarray(pos, jnp.int32)
    return pos % slots if cfg.sliding_window else pos


def gqa_decode(p, cfg, x, cache_k, cache_v, slot_pos, cur_pos, slot, *,
               use_rope: bool = True, valid_from=None):
    """One token.  x: (B,1,d).  Caches (B,S,KH,D), read only; slot_pos (S,)
    absolute positions per slot.  Batch is position-aligned (continuous
    batching with aligned steps — see serve/engine.py); ``valid_from`` (B,)
    masks each row's cache below its own admission boundary.

    Returns ``(out, k, v)``: the token's K/V (B,1,KH,D) are attended as
    they are and left for the caller to write into ``slot``
    (:func:`cache_slot`), whose old occupant is masked."""
    b = x.shape[0]
    q, k, v = _qkv(p, cfg, x)
    cur = jnp.asarray(cur_pos, jnp.int32)
    if use_rope:
        cos, sin = rope_tables(cur[None], cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    k_pos = jnp.where(jnp.arange(slot_pos.shape[0]) == slot, -1, slot_pos)
    out = decode_attention(q, cache_k, cache_v, k_pos, cur,
                           window=cfg.sliding_window, valid_from=valid_from,
                           k_new=k, v_new=v)
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim)
    return linear(out, p["wo"], name="wo"), k, v


def cross_decode(p, cfg, x, cross_k, cross_v):
    """Decoder cross-attention step: q from x, cached K/V from the encoder
    (computed ONCE per utterance — the pre-pack data-reuse story)."""
    b = x.shape[0]
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = linear(x, p["wq"], p.get("bq"), name="wq").reshape(b, 1, h, hd)
    kpos = jnp.arange(cross_k.shape[1])
    out = decode_attention(q, cross_k, cross_v, kpos, cross_k.shape[1] - 1)
    return linear(out.reshape(b, 1, h * hd), p["wo"], name="wo")


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank q/kv, decoupled rope, absorbed decode
# ---------------------------------------------------------------------------


def init_mla(rng, cfg):
    d, h = cfg.d_model, cfg.num_heads
    dn, dr, dv = cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    pt = ParamTree(rng, cfg.dtype)
    if qr:
        pt.dense("wq_a", (d, qr), ("embed", "lora"))
        pt.ones("q_norm", (qr,), ("lora",))
        pt.dense("wq_b", (qr, h * (dn + dr)), ("lora", "qheads"))
    else:
        pt.dense("wq", (d, h * (dn + dr)), ("embed", "qheads"))
    pt.dense("wkv_a", (d, kvr + dr), ("embed", "lora"))
    pt.ones("kv_norm", (kvr,), ("lora",))
    pt.dense("wkv_b", (kvr, h * (dn + dv)), ("lora", "qheads"))
    pt.dense("wo", (h * dv, d), ("qheads", "embed"))
    return pt.build()


def mla_scale(cfg) -> float:
    """MLA's softmax scale: (dn + dr) ** -0.5, times mscale(factor,
    mscale_all_dim) squared under YaRN (DeepSeek-V2's attention)."""
    scale = (cfg.head_dim + cfg.rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_q(p, cfg, x):
    """x (..., d) -> q (..., h * (dn + dr)): through the Q LoRA, or one
    direct projection when ``q_lora_rank`` is 0."""
    if not cfg.q_lora_rank:
        return linear(x, p["wq"], name="wq")
    cq = rmsnorm(linear(x, p["wq_a"], name="wq_a"), p["q_norm"],
                 cfg.norm_eps)
    return linear(cq, p["wq_b"], name="wq_b")


def _mla_qkv_train(p, cfg, x, pos):
    b, s, _ = x.shape
    h, dn, dr, dv = cfg.num_heads, cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    q = _mla_q(p, cfg, x).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    ckv = linear(x, p["wkv_a"], name="wkv_a")
    c_kv = rmsnorm(ckv[..., : cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = ckv[..., cfg.kv_lora_rank:][:, :, None, :]      # (B,S,1,dr)
    kv = linear(c_kv, p["wkv_b"], name="wkv_b").reshape(b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    cos, sin = rope_tables(pos, dr, cfg.rope_theta, yarn_of(cfg))
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (b, s, h, dr))], axis=-1)
    return q_full, k_full, v, c_kv, k_rope[:, :, 0, :]


def mla_forward(p, cfg, x, *, pos_offset=0, chunk: int = 512,
                valid_from=None):
    """Train/prefill MLA.  Returns (out, (c_kv, k_rope)) for the cache."""
    b, s, _ = x.shape
    pos = pos_offset + jnp.arange(s)
    q, k, v, c_kv, k_rope = _mla_qkv_train(p, cfg, x, pos)
    out = chunked_attention(q, k, v, causal=True, chunk=chunk,
                            q_offset=pos_offset, valid_from=valid_from,
                            scale=mla_scale(cfg))
    out = out.reshape(b, s, cfg.num_heads * cfg.v_head_dim)
    return linear(out, p["wo"], name="wo"), (c_kv, k_rope)


def mla_decode(p, cfg, x, cache_c, cache_kr, cur_pos, slot, *,
               valid_from=None):
    """Absorbed-matrix decode over the compressed cache, read only.

    cache_c: (B,S,kvr)  cache_kr: (B,S,dr).  The q_nope->c-space and
    c->v absorbtions avoid materializing per-head K/V for 32k positions;
    they take ``wkv_b`` as per-head einsum operands, so it stays unpacked
    (``serve.engine.PACKABLE``).  The scores, softmax and values run
    under the ``attention`` scope.

    Returns ``(out, c, kr)``: the token's compressed entries (B,1,kvr) and
    (B,1,dr) are attended as they are and left for the caller to write
    into ``slot`` (:func:`cache_slot`), whose old occupant is masked.
    """
    b = x.shape[0]
    h, dn, dr, dv, kvr = (cfg.num_heads, cfg.head_dim, cfg.rope_head_dim,
                          cfg.v_head_dim, cfg.kv_lora_rank)
    q = _mla_q(p, cfg, x).reshape(b, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    cos, sin = rope_tables(jnp.asarray([cur_pos]), dr, cfg.rope_theta,
                           yarn_of(cfg))
    q_rope = apply_rope(q_rope[:, None], cos, sin)[:, 0]     # (B,h,dr)

    ckv = linear(x[:, 0], p["wkv_a"], name="wkv_a")
    c_new = rmsnorm(ckv[..., :kvr], p["kv_norm"], cfg.norm_eps)
    kr_new = ckv[..., kvr:]
    kr_new = apply_rope(kr_new[:, None, None], cos, sin)[:, 0, 0]

    with jax.named_scope("attention"):
        w = p["wkv_b"].reshape(kvr, h, dn + dv)
        w_uk, w_uv = w[..., :dn], w[..., dn:]
        q_c = jnp.einsum("bhd,chd->bhc", q_nope, w_uk,
                         preferred_element_type=jnp.float32)  # into c-space
        q_rope = q_rope.astype(jnp.float32)
        scale = mla_scale(cfg)
        s = (jnp.einsum("bhc,bsc->bhs", q_c, cache_c.astype(jnp.float32))
             + jnp.einsum("bhr,bsr->bhs", q_rope,
                          cache_kr.astype(jnp.float32)))
        s = s * scale
        pos_s = jnp.arange(cache_c.shape[1])
        valid = (pos_s <= cur_pos) & (pos_s != slot)
        if valid_from is not None:
            s = jnp.where((valid[None, :] &
                           (pos_s[None, :] >= valid_from[:, None]))[:, None],
                          s, NEG_INF)
        else:
            s = jnp.where(valid[None, None], s, NEG_INF)
        c_f = c_new.astype(jnp.float32)
        s_new = (jnp.einsum("bhc,bc->bh", q_c, c_f)
                 + jnp.einsum("bhr,br->bh", q_rope,
                              kr_new.astype(jnp.float32)))
        s = jnp.concatenate([s, (s_new * scale)[..., None]], axis=-1)
        pattn = jax.nn.softmax(s, axis=-1)
        o_c = (jnp.einsum("bhs,bsc->bhc", pattn[..., :-1],
                          cache_c.astype(jnp.float32))
               + pattn[..., -1:] * c_f[:, None])
        o = jnp.einsum("bhc,chv->bhv", o_c, w_uv).astype(x.dtype)
    out = linear(o.reshape(b, 1, h * dv), p["wo"], name="wo")
    return out, c_new[:, None], kr_new[:, None]
