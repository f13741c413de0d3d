"""Jit'd wrappers around the Pallas TSMM kernels.

Responsibilities:
  * pad operands to kernel-legal shapes (sublane x 128 tiles) and slice
    the result back;
  * select the implementation: ``pallas`` on TPU, ``pallas_interpret``
    (Python emulation) for CPU validation, ``xla`` — a blocked einsum that
    is bit-for-bit the same math on the same packed layout, used for the
    dry-run lowering and CPU serving (Pallas cannot compile for the CPU
    backend);
  * expose pack/unpack as jitted ops.

Layer cake: ``repro.core`` decides *what* to run (plans, packing policy);
this module only knows *how* to run a given blocked matmul.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import tsmm as _k


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(impl: Optional[str]) -> str:
    return default_impl() if impl in (None, "auto") else impl


def _ceil_to(x: int, q: int) -> int:
    return -(-x // q) * q


def sublane(dtype) -> int:
    return {"float32": 8, "bfloat16": 16, "float16": 16}.get(str(jnp.dtype(dtype)), 8)


def pad2(x, m, n):
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm == 0 and pn == 0:
        return x
    return jnp.pad(x, ((0, pm), (0, pn)))


# ---------------------------------------------------------------------------
# packing ops (jnp — a one-time layout transform, not a hot loop)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bm", "bk", "impl", "alpha"))
def pack_blocks(a, bm: int, bk: int, alpha: float = 1.0,
                impl: Optional[str] = None):
    """(M, K) -> (nm, nk, bm, bk) block-major, zero-padded, alpha folded.

    ``impl='pallas'`` uses the on-device re-tile kernel (TPU);
    default is the jnp reshape/transpose (XLA handles it fine — packing
    is a one-time cost, but the kernel keeps the HBM traffic at exactly
    2x the operand instead of XLA's layout-dependent copies)."""
    impl = _resolve(impl) if impl else "xla"
    if impl in ("pallas", "pallas_interpret"):
        mp = _ceil_to(a.shape[0], bm)
        kp = _ceil_to(a.shape[1], bk)
        return _k.pack_blocks_kernel(pad2(a, mp, kp), bm, bk, alpha=alpha,
                                     interpret=(impl == "pallas_interpret"))
    return _ref.pack_ref(a, bm, bk, alpha=alpha)


@functools.partial(jax.jit, static_argnames=("m", "k"))
def unpack_blocks(ap, m: int, k: int):
    return _ref.unpack_ref(ap, m, k)


# ---------------------------------------------------------------------------
# blocked-XLA equivalents (same packed layout, same blocking, XLA codegen)
# ---------------------------------------------------------------------------


def _xla_packed_a(ap, b, bias=None, act=None):
    nm, nk, bm, bk = ap.shape
    bb = b.reshape(nk, bk, b.shape[1])
    # (nm,nk,bm,bk) x (nk,bk,n) -> (nm,bm,n): contract blocked k exactly as
    # the kernel's grid does, fp32 accumulation; bias+act apply to the
    # fp32 result inside the same program, mirroring the fused epilogue.
    out = jnp.einsum(
        "mkab,kbn->man", ap, bb, preferred_element_type=jnp.float32
    ).reshape(nm * bm, b.shape[1])
    if bias is not None:
        out = out + bias.astype(jnp.float32)[None, :]
    return _ref.act_ref(out, act).astype(b.dtype)


def _xla_skinny_a(x, wp, bias, act):
    nk, nn, bk, bn = wp.shape
    xb = x.reshape(x.shape[0], nk, bk)
    out = jnp.einsum(
        "mkb,knbc->mnc", xb, wp, preferred_element_type=jnp.float32
    ).reshape(x.shape[0], nn * bn)
    if bias is not None:
        out = out + bias.astype(jnp.float32)[None, :]
    out = _ref.act_ref(out, act)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# public ops
# ---------------------------------------------------------------------------


def _pad_bias(bias, npad: int):
    if bias is None:
        return None
    return jnp.pad(bias, (0, npad - bias.shape[0]))


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "act", "impl", "dims",
                                    "m_split", "name"))
def tsmm(a, b, bias=None, *, bm: int = 512, bk: int = 512,
         act: Optional[str] = None, impl: Optional[str] = None,
         dims: tuple = (), m_split: int = 1, name: Optional[str] = None):
    """Unpacked tall-A TSMM: C = act(A @ B + bias) (pads + slices
    internally).  The epilogue is fused into the kernel's final k step
    (DESIGN.md §11); ``dims``/``m_split`` are the plan's grid schedule."""
    impl = _resolve(impl)
    m, k = a.shape
    n = b.shape[1]
    if impl == "ref":
        return _ref.tsmm_ref(a, b, bias=bias, act=act)
    bm_ = min(bm, _ceil_to(m, sublane(a.dtype)))
    mp, kp = _ceil_to(m, bm_), _ceil_to(k, bk)
    npad = _ceil_to(n, 128)
    ap_, bp_ = pad2(a, mp, kp), pad2(b, kp, npad)
    if impl == "xla":
        # slice BEFORE the epilogue: XLA fuses bias/act into the dot's
        # consumer either way, but the activation then runs on the real
        # (m, n) output, not the 128-padded columns (a Pallas kernel pays
        # nothing for the pad — the VPU tile is 128 lanes regardless)
        out = jnp.dot(ap_, bp_, preferred_element_type=jnp.float32)[:m, :n]
        if bias is not None:
            out = out + bias.astype(jnp.float32)[None, :]
        return _ref.act_ref(out, act).astype(a.dtype)
    out = _k.tsmm_tall_a(ap_, bp_, _pad_bias(bias, npad), bm=bm_, bk=bk,
                         act=act, dims=dims, m_split=m_split,
                         interpret=(impl == "pallas_interpret"), name=name)
    return out[:m, :n]


@functools.partial(jax.jit,
                   static_argnames=("act", "impl", "dims", "m_split",
                                    "name"))
def tsmm_packed(ap, b, bias=None, *, act: Optional[str] = None,
                impl: Optional[str] = None, dims: tuple = (),
                m_split: int = 1, name: Optional[str] = None):
    """Packed tall-A TSMM: C = act(unpack(Ap) @ B + bias).
    Ap (nm,nk,bm,bk); fused epilogue + grid schedule as in ``tsmm``."""
    impl = _resolve(impl)
    nm, nk, bm, bk = ap.shape
    n = b.shape[1]
    npad = _ceil_to(n, 128)
    bp_ = pad2(b, nk * bk, npad)
    biasp = _pad_bias(bias, npad)
    if impl == "xla":
        out = _xla_packed_a(ap, bp_, biasp, act)
    else:
        out = _k.tsmm_packed_a(ap, bp_, biasp, act=act, dims=dims,
                               m_split=m_split,
                               interpret=(impl == "pallas_interpret"),
                               name=name)
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("act", "impl", "dims", "name"))
def tsmm_skinny(x, wp, bias=None, *, act: Optional[str] = None,
                impl: Optional[str] = None, dims: tuple = (),
                name: Optional[str] = None, layer=None):
    """Skinny-A x packed-W with fused epilogue: act(X @ W + bias).

    X (m, K) — m is the skinny dim (decode batch); Wp (nk, nn, bk, bn),
    or the layer-stacked (L, nk, nn, bk, bn) read at row ``layer``.
    """
    impl = _resolve(impl)
    m, k = x.shape
    nk, nn, bk, bn = wp.shape[-4:]
    n = nn * bn
    biasp = None if bias is None else jnp.pad(bias, (0, n - bias.shape[0]))
    if impl == "xla":
        if layer is not None:
            wp = wp[layer]
        out = _xla_skinny_a(pad2(x, m, nk * bk), wp, biasp, act)
        return out[:, : (bias.shape[0] if bias is not None else n)]
    mp = _ceil_to(m, sublane(x.dtype))
    xp = pad2(x, mp, nk * bk)
    out = _k.tsmm_skinny_a(xp, wp, biasp, act=act, dims=dims,
                           interpret=(impl == "pallas_interpret"), name=name,
                           layer=layer)
    return out[:m, : (bias.shape[0] if bias is not None else n)]
