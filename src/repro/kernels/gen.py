"""Parameterized Pallas kernel generator (DESIGN.md §14).

One emitter per orientation replaces the PR-4 hand-written variant zoo:
:func:`emit_tall_a` / :func:`emit_skinny_a` lower ANY valid
:class:`~repro.kernels.variants.grammar.GenSpec` grammar point to a
kernel program.  The grammar axes map onto kernel structure as follows:

* ``loop=kinner``  — K is the innermost grid axis; each output block's
  accumulator is revisited on consecutive steps (the Pallas
  revisiting-grid contract the PR-3 kernels established).
* ``loop=kouter``  — the K walk lives at the XLA level: a ``fori_loop``
  of single-k-slice Pallas passes with an ``input_output_aliases`` fp32
  accumulator (a Pallas output block only persists across CONSECUTIVE
  grid steps, so a (nk, nm) grid would read stale VMEM on real TPU).
* ``ksplit>1``     — the contraction is cut into independent partial-sum
  groups behind an extra parallel grid axis; the caller-side
  ``sum(axis=0)`` is the fused reduction (same jit program).
* ``acc=vmem``     — fp32 scratch accumulator in VMEM;
  ``acc=revisit``  — the (fp32) output block IS the accumulator, and a
  cast pass over the output pays the precision bill afterwards.
* ``bres=resident``— the streamed operand (B for tall-A, X for skinny-A)
  gets a constant index map (fetched once, whole-operand VMEM residency)
  and the kernel ``pl.ds``-slices its K panel per step.
* ``epi``          — ``fused`` applies bias+activation in the kernel
  epilog (or on the fp32 reduction for ``postreduce``); ``split`` leaves
  the kernel output raw and runs :func:`_split_epilogue` as a separate
  jitted pass (an extra output round trip the cost model charges).
* ``packfuse``     — skinny-A only: the natural-layout (K, N) weight is
  read with a strided index map inside the kernel, skipping the per-call
  pack pass entirely.

``impl='xla'`` lowers each point to its blocked-einsum twin (same math,
same blocking, same epilogue placement) — that is what CPU containers
time, so generated-vs-legacy comparisons measure schedule structure, not
Pallas availability.  The baseline point delegates to ``ops.tsmm*`` so
pre-grammar measurement records keep timing the identical jit programs.

Wrappers stay un-jitted at the top level on purpose: per-call eager work
(the skinny regime's per-call weight pack for non-``packfuse`` points)
must stay visible to the evaluator's timed region.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.core.plan import DEFAULT_SCHEDULE
from repro.kernels import ops
from repro.kernels import ref as _ref
from repro.kernels import tsmm as _k
from repro.kernels.ops import _ceil_to, _pad_bias
from repro.kernels.variants.grammar import BASELINE_POINT, GenSpec


def split_divisor(nk: int, want: int) -> int:
    """Largest divisor of ``nk`` that is <= ``want`` (>= 1) — the runtime
    clamp for k-split plans whose block count the requested split does not
    divide (env-override plans; enumerated plans are gated by
    ``vmem_model.feasible``)."""
    d = max(1, min(int(want), int(nk)))
    while nk % d:
        d -= 1
    return d


def _pad_natural(a, b, bm, bk):
    """Pad a natural-layout (a, b) pair to kernel-legal multiples; returns
    (a_pad, b_pad, bm_eff) — same policy as ``ops.tsmm``."""
    m, k = a.shape
    n = b.shape[1]
    bm_ = min(bm, _ceil_to(m, ops.sublane(a.dtype)))
    mp, kp = _ceil_to(m, bm_), _ceil_to(k, bk)
    npad = _ceil_to(n, 128)
    return ops.pad2(a, mp, kp), ops.pad2(b, kp, npad), bm_


def _pad_b_for_packed(ap, b):
    nm, nk, bm, bk = ap.shape
    return ops.pad2(b, nk * bk, _ceil_to(b.shape[1], 128))


def _epilogue_f32(out, bias, act, dtype):
    """Bias+activation on an fp32 result INSIDE the producing jit program
    (the post-reduce epilogue of the k-split points, and the cast-pass
    epilogue of kouter/revisit points): XLA fuses it into the consumer,
    so no separate pass over the (M, N) output."""
    if bias is not None:
        out = out + bias.astype(jnp.float32)[None, :]
    return _ref.act_ref(out, act).astype(dtype)


@functools.partial(jax.jit, static_argnames=("act",))
def _split_epilogue(out, bias, act):
    """The ``epi=split`` second pass over the CAST output (the kernel
    already wrote the result in the output dtype): bias+act on the VPU,
    extra read+write — exactly the traffic the cost model charges."""
    o = out.astype(jnp.float32)
    if bias is not None:
        o = o + bias.astype(jnp.float32)[None, :]
    return _ref.act_ref(o, act).astype(out.dtype)


# ---------------------------------------------------------------------------
# tall-A Pallas builders (one per loop-order family)
# ---------------------------------------------------------------------------


def _tall_kinner(a, b, bias, *, bm, bk, act, packed, resident, revisit,
                 dims, m_split, interpret, name=None):
    """K-innermost tall-A program for any (bres, acc, fused-epi) choice.

    ``resident`` pins the whole B in VMEM (constant index map) and slices
    its k panel with ``pl.ds``; ``revisit`` drops the VMEM scratch and
    accumulates straight into the fp32 output block (the output is then
    fp32 — the caller casts).  With a VMEM accumulator the output is
    written once, in the output dtype, with bias/act fused into the final
    k step's ``_done`` write."""
    if packed:
        nm, nk, bm, bk = a.shape
        m, k = nm * bm, nk * bk
    else:
        m, k = a.shape
        assert m % bm == 0 and k % bk == 0, (a.shape, bm, bk)
        nm, nk = m // bm, k // bk
    assert b.shape[0] == k, (a.shape, b.shape)
    n = b.shape[1]
    grid, k_axis, row, default = _k._tall_grid(nm, nk, m_split)
    if row is None:
        a_spec = (pl.BlockSpec((1, 1, bm, bk), lambda i, j: (i, j, 0, 0))
                  if packed else pl.BlockSpec((bm, bk), lambda i, j: (i, j)))
        b_spec = (pl.BlockSpec((k, n), lambda i, j: (0, 0)) if resident
                  else pl.BlockSpec((bk, n), lambda i, j: (j, 0)))
        o_spec = pl.BlockSpec((bm, n), lambda i, j: (i, 0))
        bias_spec = pl.BlockSpec((1, n), lambda i, j: (0, 0))
    else:
        a_spec = (pl.BlockSpec((1, 1, bm, bk),
                               lambda p, i, j: (row(p, i), j, 0, 0))
                  if packed else
                  pl.BlockSpec((bm, bk), lambda p, i, j: (row(p, i), j)))
        b_spec = (pl.BlockSpec((k, n), lambda p, i, j: (0, 0)) if resident
                  else pl.BlockSpec((bk, n), lambda p, i, j: (j, 0)))
        o_spec = pl.BlockSpec((bm, n), lambda p, i, j: (row(p, i), 0))
        bias_spec = pl.BlockSpec((1, n), lambda p, i, j: (0, 0))
    in_specs = [a_spec, b_spec]
    args = [a, b]
    has_bias = bias is not None
    if has_bias:
        assert bias.shape == (n,), (bias.shape, n)
        in_specs.append(bias_spec)
        args.append(bias.reshape(1, n))

    def kernel(*refs):
        a_ref, b_ref = refs[0], refs[1]
        bias_ref = refs[2] if has_bias else None
        o_ref = refs[3] if has_bias else refs[2]
        acc_ref = o_ref if revisit else refs[-1]
        j = pl.program_id(k_axis)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        blk_b = b_ref[pl.ds(j * bk, bk), :] if resident else b_ref[...]
        acc_ref[...] += jnp.dot(_k._blk(a_ref, packed), blk_b,
                                preferred_element_type=jnp.float32)

        @pl.when(j == nk - 1)
        def _done():
            o_ref[...] = _k._epilogue(acc_ref[...], bias_ref,
                                      act).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(
            (m, n), jnp.float32 if revisit else b.dtype),
        scratch_shapes=([] if revisit
                        else [pltpu.VMEM((bm, n), jnp.float32)]),
        compiler_params=_k._compiler_params(_k._semantics(dims, default)),
        interpret=interpret,
        name=name,
    )(*args)


def _tall_ksplit(a, b, *, bm, bk, splits, packed, resident, dims, interpret,
                 name=None):
    """K-split tall-A: ``splits`` independent partial sums (one parallel
    grid dim), fp32 partials out (splits, M, N); the caller's
    ``sum(axis=0)`` is the fused reduction.  ``resident`` pins the whole
    B and slices the group-local k panel from it."""
    if packed:
        nm, nk, bm, bk = a.shape
        m = nm * bm
    else:
        m, k = a.shape
        assert m % bm == 0 and k % bk == 0, (a.shape, bm, bk)
        nm, nk = m // bm, k // bk
    kfull = nk * bk
    n = b.shape[1]
    assert nk % splits == 0, (nk, splits)
    nki = nk // splits
    if packed:
        a_spec = pl.BlockSpec((1, 1, bm, bk),
                              lambda i, s, j: (i, s * nki + j, 0, 0))
    else:
        a_spec = pl.BlockSpec((bm, bk), lambda i, s, j: (i, s * nki + j))
    b_spec = (pl.BlockSpec((kfull, n), lambda i, s, j: (0, 0)) if resident
              else pl.BlockSpec((bk, n), lambda i, s, j: (s * nki + j, 0)))

    def kernel(a_ref, b_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if resident:
            jg = pl.program_id(1) * nki + pl.program_id(2)
            blk_b = b_ref[pl.ds(jg * bk, bk), :]
        else:
            blk_b = b_ref[...]
        acc_ref[...] += jnp.dot(_k._blk(a_ref, packed), blk_b,
                                preferred_element_type=jnp.float32)

        @pl.when(pl.program_id(2) == nki - 1)
        def _done():
            o_ref[0] = acc_ref[...]

    return pl.pallas_call(
        kernel,
        grid=(nm, splits, nki),
        in_specs=[a_spec, b_spec],
        out_specs=pl.BlockSpec((1, bm, n), lambda i, s, j: (s, i, 0)),
        out_shape=jax.ShapeDtypeStruct((splits, m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, n), jnp.float32)],
        compiler_params=_k._compiler_params(
            _k._semantics(dims, ("parallel", "parallel", "arbitrary"))),
        interpret=interpret,
        name=name,
    )(a, b)


def _tall_kouter(a, b, *, bm, bk, packed, dims, interpret, name=None):
    """K-outermost loop order: each k step sweeps every output row panel,
    accumulating into an fp32 output revisited in HBM.  B's k-block is
    fetched ONCE per k step (vs once per row panel for kinner) at the
    cost of output-revisit traffic.  Returns fp32 (M, N); caller casts.

    The k loop lives at the XLA level (``fori_loop`` of single-k-slice
    Pallas passes with an aliased fp32 accumulator): a Pallas output
    block only persists across CONSECUTIVE grid steps, so a (nk, nm)
    grid revisiting block ``i`` at non-adjacent steps would read stale
    VMEM on real TPU.  Each pass here visits every output block exactly
    once — well-defined everywhere — while keeping the schedule's
    traffic shape."""
    if packed:
        nm, nk, bm, bk = a.shape
        m = nm * bm
    else:
        m, k = a.shape
        assert m % bm == 0 and k % bk == 0, (a.shape, bm, bk)
        nm, nk = m // bm, k // bk
    n = b.shape[1]
    if packed:
        a_spec = pl.BlockSpec((1, 1, bm, bk), lambda i: (i, 0, 0, 0))
    else:
        a_spec = pl.BlockSpec((bm, bk), lambda i: (i, 0))

    def kernel(a_ref, b_ref, acc_ref, o_ref):
        o_ref[...] = acc_ref[...] + jnp.dot(
            _k._blk(a_ref, packed), b_ref[...],
            preferred_element_type=jnp.float32)

    call = pl.pallas_call(
        kernel,
        grid=(nm,),
        in_specs=[
            a_spec,
            pl.BlockSpec((bk, n), lambda i: (0, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        input_output_aliases={2: 0},
        compiler_params=_k._compiler_params(
            _k._semantics(dims, ("arbitrary",))),
        interpret=interpret,
        name=name,
    )

    def step(j, acc):
        if packed:
            a_j = jax.lax.dynamic_slice(a, (0, j, 0, 0), (nm, 1, bm, bk))
        else:
            a_j = jax.lax.dynamic_slice(a, (0, j * bk), (m, bk))
        b_j = jax.lax.dynamic_slice(b, (j * bk, 0), (bk, n))
        return call(a_j, b_j, acc)

    return jax.lax.fori_loop(0, nk, step, jnp.zeros((m, n), jnp.float32))


# ---------------------------------------------------------------------------
# skinny-A Pallas builders
# ---------------------------------------------------------------------------


def _skinny_kinner(x, w, bias, *, bk, bn, act, natural, resident, revisit,
                   dims, interpret, name=None, layer=None):
    """K-innermost skinny-A program.  ``natural`` reads W in its (K, N)
    layout with a strided index map (the packfuse axis — no per-call pack
    pass); ``resident`` pins the whole X row panel (constant map) and
    ``pl.ds``-slices its k panel; ``revisit`` accumulates into the fp32
    output block instead of VMEM scratch (caller casts).  ``layer``: W is
    layer-stacked (``tsmm.pallas_call``)."""
    m, k = x.shape
    if natural:
        kw, n = w.shape
        assert k == kw and kw % bk == 0 and n % bn == 0, (x.shape, w.shape,
                                                          bk, bn)
        nk, nn = kw // bk, n // bn
    else:
        nk, nn, bk, bn = w.shape[-4:]
        assert k == nk * bk, (x.shape, w.shape)
        n = nn * bn
    x_spec = (pl.BlockSpec((m, k), lambda i, j: (0, 0)) if resident
              else pl.BlockSpec((m, bk), lambda i, j: (0, j)))
    w_spec = (pl.BlockSpec((bk, bn), lambda i, j: (j, i)) if natural
              else pl.BlockSpec((1, 1, bk, bn), lambda i, j: (j, i, 0, 0)))
    in_specs = [x_spec, w_spec]
    args = [x, w]
    has_bias = bias is not None
    if has_bias:
        assert bias.shape == (n,), (bias.shape, n)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, i)))
        args.append(bias.reshape(1, n))

    def kernel(*refs):
        x_ref, w_ref = refs[0], refs[1]
        bias_ref = refs[2] if has_bias else None
        o_ref = refs[3] if has_bias else refs[2]
        acc_ref = o_ref if revisit else refs[-1]
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        blk_x = x_ref[:, pl.ds(j * bk, bk)] if resident else x_ref[...]
        acc_ref[...] += jnp.dot(blk_x, _k._blk(w_ref, not natural),
                                preferred_element_type=jnp.float32)

        @pl.when(j == nk - 1)
        def _done():
            o_ref[...] = _k._epilogue(acc_ref[...], bias_ref,
                                      act).astype(o_ref.dtype)

    return _k.pallas_call(
        kernel,
        grid=(nn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct(
            (m, n), jnp.float32 if revisit else x.dtype),
        scratch_shapes=([] if revisit
                        else [pltpu.VMEM((m, bn), jnp.float32)]),
        compiler_params=_k._compiler_params(
            _k._semantics(dims, ("parallel", "arbitrary"))),
        interpret=interpret,
        name=name,
        layer=layer,
    )(*args)


def _skinny_ksplit(x, w, *, bk, bn, splits, natural, resident, dims,
                   interpret, name=None, layer=None):
    """K-split skinny-A: fp32 partials out (splits, m, N); caller reduces
    + applies the epilogue.  ``natural`` strides the (K, N) weight
    directly; ``resident`` pins the whole X and slices the group-local k
    panel; ``layer`` as in :func:`_skinny_kinner`."""
    m, k = x.shape
    if natural:
        kw, nw = w.shape
        assert kw % bk == 0 and nw % bn == 0, (w.shape, bk, bn)
        nk, nn = kw // bk, nw // bn
    else:
        nk, nn, bk, bn = w.shape[-4:]
    assert k == nk * bk, (x.shape, w.shape)
    n = nn * bn
    assert nk % splits == 0, (nk, splits)
    nki = nk // splits
    x_spec = (pl.BlockSpec((m, k), lambda i, s, j: (0, 0)) if resident
              else pl.BlockSpec((m, bk), lambda i, s, j: (0, s * nki + j)))
    if natural:
        w_spec = pl.BlockSpec((bk, bn), lambda i, s, j: (s * nki + j, i))
    else:
        w_spec = pl.BlockSpec((1, 1, bk, bn),
                              lambda i, s, j: (s * nki + j, i, 0, 0))

    def kernel(x_ref, w_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if resident:
            jg = pl.program_id(1) * nki + pl.program_id(2)
            blk_x = x_ref[:, pl.ds(jg * bk, bk)]
        else:
            blk_x = x_ref[...]
        acc_ref[...] += jnp.dot(blk_x, _k._blk(w_ref, not natural),
                                preferred_element_type=jnp.float32)

        @pl.when(pl.program_id(2) == nki - 1)
        def _done():
            o_ref[0] = acc_ref[...]

    return _k.pallas_call(
        kernel,
        grid=(nn, splits, nki),
        in_specs=[x_spec, w_spec],
        out_specs=pl.BlockSpec((1, m, bn), lambda i, s, j: (s, 0, i)),
        out_shape=jax.ShapeDtypeStruct((splits, m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=_k._compiler_params(
            _k._semantics(dims, ("parallel", "parallel", "arbitrary"))),
        interpret=interpret,
        name=name,
        layer=layer,
    )(x, w)


# ---------------------------------------------------------------------------
# jitted compute programs (one per grammar point x blocks x impl)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("g", "bm", "bk", "act", "packed", "impl",
                                    "dims", "m_split", "name"))
def _tall_compute(a, b, bias, *, g, bm, bk, act, packed, impl, dims,
                  m_split, name=None):
    """One program per (grammar point, blocks, act, impl, schedule).
    ``bias``/``act`` arrive pre-gated by the wrapper: None for
    ``epi=split`` points (raw output; the wrapper runs the separate
    pass), the real epilogue otherwise."""
    n = b.shape[1]
    out_dtype = b.dtype
    if impl == "xla":
        if g.ksplit > 1:
            if packed:
                nm, nk, pbm, pbk = a.shape
                nki = nk // g.ksplit
                parts = jnp.einsum("msjab,sjbn->sman",
                                   a.reshape(nm, g.ksplit, nki, pbm, pbk),
                                   b.reshape(g.ksplit, nki, pbk, n),
                                   preferred_element_type=jnp.float32)
                parts = parts.reshape(g.ksplit, nm * pbm, n)
            else:
                m = a.shape[0]
                kk = a.shape[1] // g.ksplit
                parts = jnp.einsum("msk,skn->smn",
                                   a.reshape(m, g.ksplit, kk),
                                   b.reshape(g.ksplit, kk, n),
                                   preferred_element_type=jnp.float32)
            return _epilogue_f32(parts.sum(axis=0), bias, act, out_dtype)
        if packed:
            return ops._xla_packed_a(a, b, bias, act)
        out = jnp.dot(a, b, preferred_element_type=jnp.float32)
        return _epilogue_f32(out, bias, act, out_dtype)
    interpret = impl == "pallas_interpret"
    if g.loop == "kouter":
        out = _tall_kouter(a, b, bm=bm, bk=bk, packed=packed, dims=dims,
                           interpret=interpret, name=name)
        # the epilogue rides the final cast pass over the fp32 accumulator
        # (already charged by the cost model's output-revisit terms)
        return _epilogue_f32(out, bias, act, out_dtype)
    if g.ksplit > 1:
        parts = _tall_ksplit(a, b, bm=bm, bk=bk, splits=g.ksplit,
                             packed=packed, resident=(g.bres == "resident"),
                             dims=dims, interpret=interpret, name=name)
        # fused reduction + epilogue inside the same program
        return _epilogue_f32(parts.sum(axis=0), bias, act, out_dtype)
    out = _tall_kinner(a, b, bias, bm=bm, bk=bk, act=act, packed=packed,
                       resident=(g.bres == "resident"),
                       revisit=(g.acc == "revisit"), dims=dims,
                       m_split=m_split, interpret=interpret, name=name)
    if g.acc == "revisit":
        out = out.astype(out_dtype)   # the cast pass the model charges
    return out


@functools.partial(jax.jit,
                   static_argnames=("g", "bk", "bn", "act", "natural",
                                    "impl", "dims", "name"))
def _skinny_compute(x, w, bias, layer=None, *, g, bk, bn, act, natural,
                    impl, dims, name=None):
    """Skinny twin of :func:`_tall_compute`; ``natural`` marks a
    packfuse point consuming the (K, N) weight layout directly;
    ``layer`` marks a layer-stacked packed W (``tsmm.pallas_call``)."""
    m = x.shape[0]
    out_dtype = x.dtype
    if natural:
        n = w.shape[1]
        nk = w.shape[0] // bk
        nn = n // bn
    else:
        nk, nn = w.shape[-4], w.shape[-3]
        n = nn * bn
    if impl == "xla":
        if layer is not None:
            w, layer = w[layer], None
        if g.ksplit > 1:
            if natural:
                kk = w.shape[0] // g.ksplit
                parts = jnp.einsum("msk,skn->smn",
                                   x.reshape(m, g.ksplit, kk),
                                   w.reshape(g.ksplit, kk, n),
                                   preferred_element_type=jnp.float32)
            else:
                nki = nk // g.ksplit
                parts = jnp.einsum("msjb,sjnbc->smnc",
                                   x.reshape(m, g.ksplit, nki, bk),
                                   w.reshape(g.ksplit, nki, nn, bk, bn),
                                   preferred_element_type=jnp.float32)
                parts = parts.reshape(g.ksplit, m, n)
            return _epilogue_f32(parts.sum(axis=0), bias, act, out_dtype)
        if natural:
            # blocked natural contraction — the same blocked-einsum
            # schedule the packed baseline times, minus its pack pass, so
            # an off-TPU measurement of packfuse vs baseline isolates
            # exactly the per-call pack cost
            out = jnp.einsum("mjb,jbn->mn", x.reshape(m, nk, bk),
                             w.reshape(nk, bk, n),
                             preferred_element_type=jnp.float32)
            return _epilogue_f32(out, bias, act, out_dtype)
        return ops._xla_skinny_a(x, w, bias, act)
    interpret = impl == "pallas_interpret"
    if g.ksplit > 1:
        parts = _skinny_ksplit(x, w, bk=bk, bn=bn, splits=g.ksplit,
                               natural=natural,
                               resident=(g.bres == "resident"), dims=dims,
                               interpret=interpret, name=name, layer=layer)
        return _epilogue_f32(parts.sum(axis=0), bias, act, out_dtype)
    out = _skinny_kinner(x, w, bias, bk=bk, bn=bn, act=act, natural=natural,
                         resident=(g.bres == "resident"),
                         revisit=(g.acc == "revisit"), dims=dims,
                         interpret=interpret, name=name, layer=layer)
    if g.acc == "revisit":
        out = out.astype(out_dtype)
    return out


# ---------------------------------------------------------------------------
# the emitters (the ONLY entry points kernels/variants dispatches through)
# ---------------------------------------------------------------------------


def emit_tall_a(g: GenSpec, a, b, bias=None, act=None, *, bm: int = 0,
                bk: int = 0, packed: bool = False, impl=None, schedule=None,
                name=None):
    """Lower grammar point ``g`` for the tall-A orientation.

    Contract matches the PR-4 variant wrappers: returns (M, N) for
    natural inputs (padding sliced off) or (nm*bm, N) for packed inputs
    (caller slices rows).  ``name`` names the kernel's custom call in the
    compiled program (None keeps Pallas's default)."""
    sch = schedule or DEFAULT_SCHEDULE
    if g == BASELINE_POINT:
        # the baseline point IS the PR-3 kernel: delegate so pre-grammar
        # measurement records keep timing identical jit programs
        if packed:
            return ops.tsmm_packed(a, b, bias, act=act, impl=impl,
                                   dims=sch.dims, m_split=sch.m_split,
                                   name=name)
        return ops.tsmm(a, b, bias, bm=bm, bk=bk, act=act, impl=impl,
                        dims=sch.dims, m_split=sch.m_split, name=name)
    impl = ops._resolve(impl)
    n = b.shape[1]
    if packed:
        nm, nk, bm, bk = a.shape
        ap, bp = a, _pad_b_for_packed(a, b)
    else:
        m = a.shape[0]
        ap, bp, bm = _pad_natural(a, b, bm, bk)
        nk = bp.shape[0] // bk
    if g.ksplit > 1:
        s = split_divisor(nk, g.ksplit)
        if s != g.ksplit:
            g = dataclasses.replace(g, ksplit=s)
    fused = g.epi != "split"
    biasp = _pad_bias(bias, bp.shape[1])
    out = _tall_compute(ap, bp, biasp if fused else None, g=g, bm=bm, bk=bk,
                        act=act if fused else None, packed=packed, impl=impl,
                        dims=sch.dims, m_split=sch.m_split, name=name)
    if not fused and (bias is not None or act not in (None, "none")):
        out = _split_epilogue(out, biasp, act)
    if packed:
        return out[:, :n]
    return out[:m, :n]


def emit_skinny_a(g: GenSpec, x, w, bias=None, act=None, *, bk: int = 0,
                  bn: int = 0, packed: bool = True, impl=None,
                  schedule=None, name=None, layer=None):
    """Lower grammar point ``g`` for the skinny-A orientation.

    ``w`` is the packed (nk, nn, bk, bn) weight when ``packed`` else the
    natural (K, N) layout — non-packfuse points then OWN the per-call
    pack cost (eager, so the evaluator times it); packfuse points read
    the natural layout inside the kernel.  A packed ``w`` may be the
    layer-stacked (L, nk, nn, bk, bn), read at row ``layer``.  Returns
    (m, n_padded) — the caller slices padded columns, as with
    ``ops.tsmm_skinny``.  ``name`` as in :func:`emit_tall_a`."""
    sch = schedule or DEFAULT_SCHEDULE
    assert layer is None or packed, "a layer index reads packed weights"
    if g.packfuse and packed:
        # weight already block-major (packed at load): nothing to fuse —
        # honest fallback to the baseline packed kernel
        return ops.tsmm_skinny(x, w, bias, act=act, impl=impl,
                               dims=sch.dims, name=name, layer=layer)
    if g == BASELINE_POINT:
        if not packed:
            # per-call pack — deliberately eager so the evaluator's timed
            # region pays it (prepack=False replay fidelity, DESIGN.md §9)
            w = packing.pack(w, bk, bn).blocks
        return ops.tsmm_skinny(x, w, bias, act=act, impl=impl,
                               dims=sch.dims, name=name, layer=layer)
    impl = ops._resolve(impl)
    m = x.shape[0]
    natural = bool(g.packfuse)
    if natural:
        k, n = x.shape[1], w.shape[1]
        kp, np_ = _ceil_to(k, bk), _ceil_to(n, bn)
        wq = ops.pad2(w, kp, np_)
        nk = kp // bk
    else:
        if not packed:
            w = packing.pack(w, bk, bn).blocks   # eager: timed per call
        nk, nn, bk, bn = w.shape[-4:]
        wq, kp, np_ = w, nk * bk, nn * bn
    xp = ops.pad2(x, _ceil_to(m, ops.sublane(x.dtype)), kp)
    if g.ksplit > 1:
        s = split_divisor(nk, g.ksplit)
        if s != g.ksplit:
            g = dataclasses.replace(g, ksplit=s)
    fused = g.epi != "split"
    biasp = _pad_bias(bias, np_)
    out = _skinny_compute(xp, wq, biasp if fused else None, layer, g=g,
                          bk=bk, bn=bn, act=act if fused else None,
                          natural=natural, impl=impl, dims=sch.dims,
                          name=name)
    if not fused and (bias is not None or act not in (None, "none")):
        out = _split_epilogue(out, biasp, act)
    return out[:m]
