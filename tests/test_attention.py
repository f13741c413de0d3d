"""Attention correctness: chunked online-softmax vs naive reference,
sliding windows, GQA grouping, MLA absorbed decode vs explicit forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as A

RNG = np.random.default_rng(7)


def naive_attention(q, k, v, *, causal=True, window=0):
    b, sq, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, sq, kh, g, d)
    s = np.einsum("bqhgd,bkhd->bhgqk", np.asarray(qg, np.float32),
                  np.asarray(k, np.float32)) / np.sqrt(d)
    qpos = np.arange(sq)[:, None]
    kpos = np.arange(k.shape[1])[None, :]
    mask = np.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= qpos - kpos < window
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("bhgqk,bkhd->bhgqd", p, np.asarray(v, np.float32))
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)


@pytest.mark.parametrize("sq,sk,h,kh,d,chunk", [
    (32, 32, 4, 4, 16, 8),
    (64, 64, 8, 2, 32, 16),     # GQA g=4
    (48, 48, 6, 3, 8, 16),      # non-pow2
    (32, 32, 4, 1, 16, 32),     # MQA, single chunk
])
@pytest.mark.parametrize("window", [0, 8])
def test_chunked_vs_naive(sq, sk, h, kh, d, chunk, window):
    q = jnp.asarray(RNG.standard_normal((2, sq, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, sk, kh, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, sk, kh, d)), jnp.float32)
    got = A.chunked_attention(q, k, v, causal=True, window=window,
                              chunk=chunk)
    want = naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_chunked_non_causal_cross():
    q = jnp.asarray(RNG.standard_normal((2, 32, 4, 16)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 48, 4, 16)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 48, 4, 16)), jnp.float32)
    got = A.chunked_attention(q, k, v, causal=False, chunk=16)
    want = naive_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_decode_attention_matches_last_row():
    """decode_attention over a filled cache == last row of full attention."""
    b, s, h, kh, d = 2, 24, 4, 2, 16
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, kh, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, kh, d)), jnp.float32)
    full = A.chunked_attention(q, k, v, causal=True, chunk=8)
    got = A.decode_attention(q[:, -1:], k, v, jnp.arange(s), s - 1)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(full[:, -1]),
                               rtol=2e-5, atol=2e-5)


def test_mla_decode_matches_forward():
    from repro.configs import get_reduced_config
    cfg = get_reduced_config("deepseek_v2_236b")
    p, _ = A.init_mla(jax.random.PRNGKey(0), cfg)
    p = jax.tree.map(lambda x: x.astype(jnp.float32), p)
    b, s = 2, 12
    x = jnp.asarray(0.1 * RNG.standard_normal((b, s, cfg.d_model)), jnp.float32)
    out_full, (c_kv, k_rope) = A.mla_forward(p, cfg, x, chunk=4)

    cache_c = jnp.zeros((b, s, cfg.kv_lora_rank), jnp.float32)
    cache_kr = jnp.zeros((b, s, cfg.rope_head_dim), jnp.float32)
    cache_c = cache_c.at[:, : s - 1].set(c_kv[:, : s - 1])
    cache_kr = cache_kr.at[:, : s - 1].set(k_rope[:, : s - 1])
    out_step, _, _ = A.mla_decode(p, cfg, x[:, -1:], cache_c, cache_kr, s - 1,
                                  s - 1)
    np.testing.assert_allclose(np.asarray(out_step[:, 0]),
                               np.asarray(out_full[:, -1]),
                               rtol=2e-4, atol=2e-4)


def _write_then_attend(p, cfg, x, ck, cv, slot_pos, cur, valid_from):
    """GQA decode as it was when the cache passed through the layer scan:
    the token's K/V written into their slot first, then the whole cache
    attended."""
    from repro.core.linear import linear
    from repro.models.layers import apply_rope, rope_tables
    q, k, v = A._qkv(p, cfg, x)
    cos, sin = rope_tables(jnp.asarray([cur]), cfg.head_dim, cfg.rope_theta)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    slot = cur % ck.shape[1]
    ck, cv = ck.at[:, slot].set(k[:, 0]), cv.at[:, slot].set(v[:, 0])
    out = A.decode_attention(q, ck, cv, slot_pos.at[slot].set(cur), cur,
                             window=cfg.sliding_window, valid_from=valid_from)
    return linear(out.reshape(x.shape[0], 1, -1), p["wo"])


# arch, cache slots, clock, slot -> position map, valid_from
GQA_CASES = {
    # a recycled pool: every slot holds a dead stream's entries, the
    # token's own slot included; row 1 was admitted at position 5
    "recycled_slot": ("glm4_9b", 16, 9, np.arange(16), (0, 5)),
    # row 0 starts with three pad positions; slots past the clock are empty
    "valid_from": ("qwen1_5_4b", 16, 9,
                   np.where(np.arange(16) < 9, np.arange(16), -1), (3, 0)),
    # 8 slots under a 16-token window: position 20 goes to slot 4, whose
    # occupant (position 12) is still inside the window
    "window_wrap": ("h2o_danube_1_8b", 8, 20, 12 + (np.arange(8) - 12) % 8,
                    None),
}


@pytest.mark.parametrize("case", sorted(GQA_CASES))
def test_gqa_decode_matches_write_then_attend(case):
    """gqa_decode reads the cache only: the token's K/V join the cached
    slots in one softmax and the slot they will be written to is masked,
    which equals writing them first and attending over the cache."""
    from repro.configs import get_reduced_config
    arch, slots, cur, slot_pos, valid_from = GQA_CASES[case]
    cfg = get_reduced_config(arch)
    p, _ = A.init_gqa(jax.random.PRNGKey(1), cfg)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    b, kh, hd = 2, cfg.num_kv_heads, cfg.head_dim
    x = jnp.asarray(RNG.standard_normal((b, 1, cfg.d_model)), jnp.float32)
    ck, cv = (jnp.asarray(RNG.standard_normal((b, slots, kh, hd)),
                          jnp.float32) for _ in range(2))
    slot_pos = jnp.asarray(slot_pos, jnp.int32)
    vf = None if valid_from is None else jnp.asarray(valid_from, jnp.int32)
    out, _, _ = A.gqa_decode(p, cfg, x, ck, cv, slot_pos, cur,
                             A.cache_slot(cfg, slots, cur), valid_from=vf)
    want = _write_then_attend(p, cfg, x, ck, cv, slot_pos, cur, vf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("valid_from", [None, (0, 3)],
                         ids=["stale_slot", "valid_from"])
def test_mla_decode_reads_cache_in_place(valid_from):
    """mla_decode attends the token's own c/kr, never its slot's stale
    occupant, and returns the entries for the caller to write."""
    from repro.configs import get_reduced_config
    cfg = get_reduced_config("deepseek_v2_236b")
    p, _ = A.init_mla(jax.random.PRNGKey(0), cfg)
    p = jax.tree.map(lambda x: x.astype(jnp.float32), p)
    b, s = 2, 12
    x = jnp.asarray(0.1 * RNG.standard_normal((b, s, cfg.d_model)), jnp.float32)
    vf = None if valid_from is None else jnp.asarray(valid_from, jnp.int32)
    out_full, (c_kv, k_rope) = A.mla_forward(p, cfg, x, chunk=4,
                                             valid_from=vf)
    # a recycled pool: junk in the token's own slot and past it
    cache_c = jnp.asarray(RNG.standard_normal((b, s + 4, cfg.kv_lora_rank)),
                          jnp.float32).at[:, : s - 1].set(c_kv[:, : s - 1])
    cache_kr = jnp.asarray(RNG.standard_normal((b, s + 4, cfg.rope_head_dim)),
                           jnp.float32).at[:, : s - 1].set(k_rope[:, : s - 1])
    out, c, kr = A.mla_decode(p, cfg, x[:, -1:], cache_c, cache_kr, s - 1,
                              s - 1, valid_from=vf)
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(out_full[:, -1]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(c[:, 0]), np.asarray(c_kv[:, -1]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(kr[:, 0]),
                               np.asarray(k_rope[:, -1]), rtol=1e-6, atol=1e-6)


def test_rope_rotation_invariant():
    """RoPE: relative-position property <q_i, k_j> depends only on i-j."""
    from repro.models.layers import apply_rope, rope_tables
    d = 32
    q = jnp.asarray(RNG.standard_normal((1, 1, 1, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 1, 1, d)), jnp.float32)

    def dot_at(pi, pj):
        cq, sq_ = rope_tables(jnp.asarray([pi]), d, 10000.0)
        ck, sk_ = rope_tables(jnp.asarray([pj]), d, 10000.0)
        qr = apply_rope(q, cq, sq_)
        kr = apply_rope(k, ck, sk_)
        return float(jnp.sum(qr * kr))

    assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-3
    assert abs(dot_at(7, 7) - dot_at(0, 0)) < 1e-3
