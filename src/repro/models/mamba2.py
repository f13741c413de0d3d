"""Mamba2 (SSD — state-space duality) block: chunked train/prefill scan and
O(1)-state decode.

The chunked SSD algorithm decomposes the sequence into Q-length chunks;
within a chunk the computation is a masked (B,Q,Q) matmul (attention-like),
across chunks a recurrent state (B,H,P,N) is carried by ``lax.scan``.  The
chunk GEMMs are Q x N x P with Q=256, N=128, P=64 — small-operand matmuls in
the tall-and-skinny family (DESIGN.md §4).

Reference semantics (tested in tests/test_mamba2.py against a sequential
scan oracle):   h_t = exp(dt_t A) h_{t-1} + dt_t * (B_t ⊗ x_t)
                y_t = C_t · h_t + D * x_t
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.linear import linear
from repro.models.layers import rmsnorm, silu
from repro.models.param import ParamTree
from repro.sharding.context import shard_act


def _dims(cfg):
    di = cfg.d_inner
    h = cfg.ssm_heads
    return di, h, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups


def init_mamba2(rng, cfg):
    d = cfg.d_model
    di, h, p_, n, g = _dims(cfg)
    conv_dim = di + 2 * g * n
    pt = ParamTree(rng, cfg.dtype)
    pt.dense("w_in", (d, 2 * di + 2 * g * n + h), ("embed", "ssm_inner"))
    pt.value("conv_w", 0.1 * jax.random.normal(
        jax.random.fold_in(rng, 101), (cfg.ssm_conv, conv_dim),
        dtype=jnp.float32).astype(cfg.dtype), ("conv", "ssm_inner"))
    pt.zeros("conv_b", (conv_dim,), ("ssm_inner",))
    a0 = jax.random.uniform(jax.random.fold_in(rng, 102), (h,),
                            minval=1.0, maxval=16.0)
    pt.value("a_log", jnp.log(a0), ("ssm_heads",))
    # dt_bias: inverse-softplus of dt ~ U[1e-3, 1e-1]
    dt0 = jnp.exp(jax.random.uniform(jax.random.fold_in(rng, 103), (h,),
                                     minval=math.log(1e-3), maxval=math.log(1e-1)))
    pt.value("dt_bias", jnp.log(jnp.expm1(dt0)), ("ssm_heads",))
    pt.ones("d_skip", (h,), ("ssm_heads",))
    pt.ones("norm", (di,), ("ssm_inner",))
    pt.dense("w_out", (di, d), ("ssm_inner", "embed"))
    return pt.build()


def _split_in(cfg, proj):
    di, h, _, n, g = _dims(cfg)
    z, xc, bc, cc, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + g * n, 2 * di + 2 * g * n], axis=-1)
    return z, jnp.concatenate([xc, bc, cc], axis=-1), dt


def _causal_conv(xbc, w, b):
    """Depthwise causal conv, width w.shape[0].  xbc: (B,S,C).

    Accumulates in fp32 so the full-sequence path matches the decode
    step's einsum (which accumulates in fp32) bit-for-bit closely enough
    for prefill/decode parity in bf16."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    out = sum(pad[:, i : i + xbc.shape[1]] * wf[i][None, None] for i in range(k))
    return silu(out + b.astype(jnp.float32)[None, None]).astype(xbc.dtype)


def _ssd_chunked(x, dt, a_neg, bmat, cmat, h0, chunk):
    """Chunked SSD scan.

    x (B,S,H,P)  dt (B,S,H)  a_neg (H,) negative  bmat/cmat (B,S,G,N).
    Returns (y (B,S,H,P), h_final (B,H,P,N) fp32).
    """
    b, s, h, p_ = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    q = min(chunk, s)
    while s % q:              # largest divisor chunk (ragged prefills)
        q -= 1
    nc = s // q
    rep = h // g

    xc = x.reshape(b, nc, q, h, p_).astype(jnp.float32)
    dtc = dt.reshape(b, nc, q, h).astype(jnp.float32)
    bc = jnp.repeat(bmat.reshape(b, nc, q, g, n), rep, axis=3).astype(jnp.float32)
    cc = jnp.repeat(cmat.reshape(b, nc, q, g, n), rep, axis=3).astype(jnp.float32)

    a = dtc * a_neg[None, None, None]            # (B,nc,Q,H), negative
    acum = jnp.cumsum(a, axis=2)                  # inclusive

    def step(hprev, inp):
        xq, dtq, bq, cq, acq = inp               # (B,Q,H,P) (B,Q,H) (B,Q,H,N) ...
        # intra-chunk (diagonal block)
        li = acq[:, :, None, :] - acq[:, None, :, :]          # (B,Qi,Qj,H)
        mask = jnp.tril(jnp.ones((xq.shape[1], xq.shape[1]), bool))
        decay = jnp.where(mask[None, :, :, None], jnp.exp(li), 0.0)
        scores = jnp.einsum("bihn,bjhn->bijh", cq, bq) * decay * dtq[:, None]
        y = jnp.einsum("bijh,bjhp->bihp", scores, xq)
        # inter-chunk (state contribution)
        y = y + jnp.einsum("bihn,bhpn,bih->bihp", cq, hprev, jnp.exp(acq))
        # state update
        dte = dtq * jnp.exp(acq[:, -1:, :] - acq)             # dt_j * decay_to_end
        s_c = jnp.einsum("bjhn,bjh,bjhp->bhpn", bq, dte, xq)
        hnew = jnp.exp(acq[:, -1])[:, :, None, None] * hprev + s_c
        return hnew, y

    xs = (xc.transpose(1, 0, 2, 3, 4), dtc.transpose(1, 0, 2, 3),
          bc.transpose(1, 0, 2, 3, 4), cc.transpose(1, 0, 2, 3, 4),
          acum.transpose(1, 0, 2, 3))
    hfin, ys = jax.lax.scan(step, h0, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, s, h, p_)
    return y, hfin


def mamba2_forward(p, cfg, x, *, h0=None, conv_init=None):
    """Full-sequence Mamba2 block.  x: (B,S,d).
    Returns (out (B,S,d), (h_final, conv_tail)) for cache handoff."""
    b, s, _ = x.shape
    di, h, p_, n, g = _dims(cfg)
    proj = linear(x, p["w_in"], name="w_in")
    z, xbc_raw, dt = _split_in(cfg, proj)
    if conv_init is not None:  # continue from cached conv tail (chunked prefill)
        full = jnp.concatenate([conv_init, xbc_raw], axis=1)
        xbc = _causal_conv(full, p["conv_w"], p["conv_b"])[:, conv_init.shape[1]:]
    else:
        xbc = _causal_conv(xbc_raw, p["conv_w"], p["conv_b"])
    conv_tail = xbc_raw[:, -(cfg.ssm_conv - 1):]  # raw inputs the decoder needs
    xs, bmat, cmat = jnp.split(xbc, [di, di + g * n], axis=-1)
    xs = shard_act(xs, "batch", "seq", "ssm_inner")
    xh = xs.reshape(b, s, h, p_)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a_neg = -jnp.exp(p["a_log"].astype(jnp.float32))
    if h0 is None:
        h0 = jnp.zeros((b, h, p_, n), jnp.float32)
    y, hfin = _ssd_chunked(xh, dtv, a_neg,
                           bmat.reshape(b, s, g, n), cmat.reshape(b, s, g, n),
                           h0, cfg.ssm_chunk)
    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(b, s, di).astype(x.dtype)
    y = rmsnorm(y * silu(z), p["norm"], cfg.norm_eps)
    out = linear(y, p["w_out"], name="w_out")
    return out, (hfin, conv_tail)


def mamba2_decode(p, cfg, x, ssm_state, conv_cache, _cur_pos):
    """One-token step.  x: (B,1,d); ssm_state (B,H,P,N) f32;
    conv_cache (B, conv-1, di+2GN) raw (pre-activation) inputs."""
    b = x.shape[0]
    di, h, p_, n, g = _dims(cfg)
    proj = linear(x[:, 0], p["w_in"], name="w_in")           # (B, ...)
    z, xbc_new, dt = _split_in(cfg, proj[:, None, :])
    z, dt = z[:, 0], dt[:, 0]
    window = jnp.concatenate([conv_cache, xbc_new], axis=1)  # (B, conv, C)
    conv_cache = window[:, 1:]
    xbc = silu(jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32))
               + p["conv_b"].astype(jnp.float32)[None]).astype(x.dtype)
    xs, bvec, cvec = jnp.split(xbc, [di, di + g * n], axis=-1)
    xh = xs.reshape(b, h, p_).astype(jnp.float32)
    bvec = jnp.repeat(bvec.reshape(b, g, n), h // g, axis=1).astype(jnp.float32)
    cvec = jnp.repeat(cvec.reshape(b, g, n), h // g, axis=1).astype(jnp.float32)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a_neg = -jnp.exp(p["a_log"].astype(jnp.float32))
    decay = jnp.exp(dtv * a_neg[None])                       # (B,H)
    ssm_state = (decay[:, :, None, None] * ssm_state
                 + dtv[:, :, None, None] * xh[..., None] * bvec[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", ssm_state, cvec)
    y = y + p["d_skip"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(b, di).astype(x.dtype)
    y = rmsnorm(y * silu(z), p["norm"], cfg.norm_eps)
    out = linear(y[:, None], p["w_out"], name="w_out")
    return out, ssm_state, conv_cache


def mamba2_ref_scan(p, cfg, x):
    """Sequential-scan ORACLE for tests: same params, same semantics,
    no chunking.  O(S) scan over single steps."""
    b, s, _ = x.shape
    di, h, p_, n, g = _dims(cfg)
    ssm = jnp.zeros((b, h, p_, n), jnp.float32)
    conv = jnp.zeros((b, cfg.ssm_conv - 1, di + 2 * g * n), x.dtype)

    def step(carry, t):
        ssm, conv = carry
        out, ssm, conv = mamba2_decode(p, cfg, jax.lax.dynamic_slice(
            x, (0, t, 0), (b, 1, x.shape[2])), ssm, conv, t)
        return (ssm, conv), out[:, 0]

    (_, _), ys = jax.lax.scan(step, (ssm, conv), jnp.arange(s))
    return ys.transpose(1, 0, 2)
