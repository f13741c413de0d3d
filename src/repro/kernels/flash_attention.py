"""Pallas TPU flash attention (causal, GQA) — beyond-paper kernel.

Why it exists here: the §Roofline table shows every prefill cell memory-
bound, and the jaxpr traffic breakdown attributes most of t_m to the
(B, H, Sq, Sk) score/prob tensors the pure-JAX chunked attention
materializes per tile.  A fused kernel keeps scores in VMEM: HBM traffic
drops to Q/K/V/O streaming — the standard flash-attention result, here as
a `pl.pallas_call` with online-softmax accumulators in VMEM scratch.

Grid: (batch, kv_head, q_blocks) parallel, kv_blocks arbitrary (innermost,
revisiting the output block — same accumulation idiom as the TSMM kernels).
Causality: kv blocks strictly above the diagonal are skipped via
``pl.when`` (no FLOPs, no DMA cost on TPU — the cost-model win the pure
JAX path cannot express).

Validated in interpret mode against models/attention.chunked_attention
(tests/test_flash_kernel.py).  The serving/dry-run paths keep the jnp
implementation on CPU; ops.flash_attention dispatches by backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tsmm import _compiler_params

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  nkv: int, bq: int, bkv: int, scale: float, causal: bool):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip blocks fully above the diagonal
    run = (not causal) or (ki * bkv <= qi * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0]                       # (bq, d) — one (b,h) per program
        k = k_ref[0]                       # (bkv, d)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(ki == nkv - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 256,
                    bkv: int = 256, interpret: bool = False):
    """q: (B, H, Sq, D)  k, v: (B, H, Sk, D)  ->  (B, H, Sq, D).

    GQA callers repeat/reshape KV heads to H before the call (zero-copy
    view under XLA).  Sq % bq == 0 and Sk % bkv == 0 (ops pads).
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    assert sq % bq == 0 and sk % bkv == 0, (sq, sk, bq, bkv)
    nq, nkv = sq // bq, sk // bkv
    scale = d ** -0.5
    kern = functools.partial(_flash_kernel, nkv=nkv, bq=bq, bkv=bkv,
                             scale=scale, causal=causal)
    bh = b * h
    qf = q.reshape(bh, sq, d)
    kf = k.reshape(bh, sk, d)
    vf = v.reshape(bh, sk, d)
    out = pl.pallas_call(
        kern,
        grid=(bh, 1, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh_, _, i, j: (bh_, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda bh_, _, i, j: (bh_, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda bh_, _, i, j: (bh_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh_, _, i, j: (bh_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
