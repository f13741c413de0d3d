"""Pallas TPU kernels for tall-and-skinny matmul (the paper's inner kernels).

Three kernels, all with fp32 VMEM accumulators and k-innermost revisiting
grids (the Pallas idiom for the paper's GEBB_t accumulation):

* ``tsmm_tall_a``      — A (M,K) tall x B (K,N) skinny, A in natural layout.
* ``tsmm_packed_a``    — same, but A is PRE-PACKED block-major
                         (nm, nk, bm, bk): each grid step DMAs one fully
                         contiguous block — the TPU analogue of the paper's
                         packed panels + per-thread headers (Fig. 3).
* ``tsmm_skinny_a``    — X (m,K) skinny x W packed (nk, nn, bk, bn) with a
                         fused bias+activation epilogue.  This is the decode
                         hot path: weights packed once at load (pre-pack
                         reuse), activations streamed.

Register blocking (m_r x n_r = 12x8 etc. in the paper) maps to the MXU:
block dims should be multiples of (sublane, 128); the autotuner enforces
that, these kernels only assert it.  ``interpret=True`` runs the kernel
body in Python on CPU — that is how this container validates them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import VMEM_LIMIT_BYTES


def _compiler_params(dimension_semantics):
    """Mosaic parameters of every kernel in this package: the grid's
    dimension semantics and the scoped-VMEM limit.  The limit is the
    same number the planner's feasibility gate budgets against
    (``core/hw.py``); without it Mosaic applies its 16 MiB default and
    refuses plans the planner admitted."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def pallas_call(kernel, *, grid, in_specs, out_specs, scratch_shapes=(),
                layer=None, **kw):
    """``pl.pallas_call`` of a skinny-A kernel whose operand 1 is a packed
    weight.  With ``layer`` (an int32 scalar) that operand is the whole
    layer-stacked array (L, nk, nn, bk, bn) and the kernel's own DMAs read
    its blocks from row ``layer``: no per-layer slice of the weight is
    made ahead of the kernel, so its HBM read stays inside the kernel."""
    if layer is None:
        return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_specs,
                              scratch_shapes=scratch_shapes, **kw)

    def shift(spec, stacked=False):
        # scalar-prefetch index maps take the scalar refs last
        f = spec.index_map
        if stacked:
            return pl.BlockSpec((None, *spec.block_shape),
                                lambda *g: (g[-1][0], *f(*g[:-1])))
        return pl.BlockSpec(spec.block_shape, lambda *g: f(*g[:-1]))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid,
        in_specs=[shift(s, i == 1) for i, s in enumerate(in_specs)],
        out_specs=shift(out_specs), scratch_shapes=scratch_shapes)
    call = pl.pallas_call(lambda layer_ref, *refs: kernel(*refs),
                          grid_spec=grid_spec, **kw)
    return lambda *args: call(
        jnp.reshape(layer, (1,)).astype(jnp.int32), *args)


def _semantics(dims, default: tuple) -> tuple:
    """Grid dimension semantics: the schedule's override when it matches
    the grid rank, else the kernel's default (a rank mismatch can only
    come from an env-override ScheduleSpec — enumerated schedules are
    gated by ``vmem_model.feasible``)."""
    dims = tuple(dims or ())
    return dims if len(dims) == len(default) else default


def _m_split_of(nm: int, m_split: int) -> int:
    """Clamp an M-partition request to a divisor of the row-panel count
    (env-override schedules; enumerated plans are gated by the vmem
    model's divisibility check)."""
    ms = max(1, min(int(m_split), nm))
    while nm % ms:
        ms -= 1
    return ms


# Bias operands enter every kernel as (1, n) rows: Mosaic tiles a rank-1
# bf16 block only in multiples of 256 lanes, a (1, bn) block in 128s.
def _epilogue(acc, bias_ref, act):
    out = acc
    if bias_ref is not None:
        out = out + bias_ref[...].astype(jnp.float32)     # (1, n) block
    if act == "relu":
        out = jnp.maximum(out, 0)
    elif act == "silu":
        out = out * (1 / (1 + jnp.exp(-out)))
    elif act == "gelu":
        out = 0.5 * out * (1 + jnp.tanh(0.7978845608028654 * (out + 0.044715 * out**3)))
    return out


# ---------------------------------------------------------------------------
# 1. tall-A, natural layout
# ---------------------------------------------------------------------------


def _tall_a_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *, nk, k_axis, act):
    @pl.when(pl.program_id(k_axis) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(k_axis) == nk - 1)
    def _done():
        o_ref[...] = _epilogue(acc_ref[...], bias_ref, act).astype(o_ref.dtype)


def _tall_a_kernel_nobias(a_ref, b_ref, o_ref, acc_ref, *, nk, k_axis, act):
    _tall_a_kernel(a_ref, b_ref, None, o_ref, acc_ref, nk=nk, k_axis=k_axis,
                   act=act)


def _tall_grid(nm: int, nk: int, m_split: int):
    """(grid, k_axis, index-map prefix arity, default semantics) for the
    row-panel tall-A kernels.  With ``m_split > 1`` the row-panel axis is
    partitioned into per-core chunks behind an extra leading PARALLEL
    grid axis (the paper's runtime thread-level M partitioning); the k
    axis stays innermost so each output block's accumulator is revisited
    on consecutive steps (the Pallas revisiting-grid contract)."""
    ms = _m_split_of(nm, m_split)
    if ms > 1:
        nmi = nm // ms
        def row(p, i):
            return p * nmi + i
        return ((ms, nmi, nk), 2, row, ("parallel", "parallel", "arbitrary"))
    return ((nm, nk), 1, None, ("parallel", "arbitrary"))


def tsmm_tall_a(a, b, bias=None, *, bm: int, bk: int, act=None,
                interpret: bool = False, dims=(), m_split: int = 1,
                name=None):
    """C = act(A @ B + bias).  A (M,K) with M % bm == 0, K % bk == 0;
    B (K,N), N is the full skinny dim kept resident per grid step (the
    paper: every worker holds the whole B block).  The epilogue is FUSED
    into the final k step's ``_done`` write — bias+activation apply to
    the fp32 accumulator while it is still in VMEM, so the (M, N) output
    never makes an extra HBM round trip (DESIGN.md §11)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % bm == 0 and k % bk == 0, (a.shape, b.shape, bm, bk)
    nm, nk = m // bm, k // bk
    grid, k_axis, row, default = _tall_grid(nm, nk, m_split)
    if row is None:
        in_specs = [pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
                    pl.BlockSpec((bk, n), lambda i, j: (j, 0))]
        o_spec = pl.BlockSpec((bm, n), lambda i, j: (i, 0))
        bias_spec = pl.BlockSpec((1, n), lambda i, j: (0, 0))
    else:
        in_specs = [pl.BlockSpec((bm, bk), lambda p, i, j: (row(p, i), j)),
                    pl.BlockSpec((bk, n), lambda p, i, j: (j, 0))]
        o_spec = pl.BlockSpec((bm, n), lambda p, i, j: (row(p, i), 0))
        bias_spec = pl.BlockSpec((1, n), lambda p, i, j: (0, 0))
    args = [a, b]
    if bias is not None:
        assert bias.shape == (n,), (bias.shape, n)
        in_specs.append(bias_spec)
        args.append(bias.reshape(1, n))
        kernel = functools.partial(_tall_a_kernel, nk=nk, k_axis=k_axis,
                                   act=act)
    else:
        kernel = functools.partial(_tall_a_kernel_nobias, nk=nk,
                                   k_axis=k_axis, act=act)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, n), jnp.float32)],
        compiler_params=_compiler_params(_semantics(dims, default)),
        interpret=interpret,
        name=name,
    )(*args)


# ---------------------------------------------------------------------------
# 2. tall-A, pre-packed block-major
# ---------------------------------------------------------------------------


def _packed_a_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *, nk, k_axis,
                     act):
    @pl.when(pl.program_id(k_axis) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[0, 0], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(k_axis) == nk - 1)
    def _done():
        o_ref[...] = _epilogue(acc_ref[...], bias_ref, act).astype(o_ref.dtype)


def _packed_a_kernel_nobias(a_ref, b_ref, o_ref, acc_ref, *, nk, k_axis, act):
    _packed_a_kernel(a_ref, b_ref, None, o_ref, acc_ref, nk=nk, k_axis=k_axis,
                     act=act)


def tsmm_packed_a(ap, b, bias=None, *, act=None, interpret: bool = False,
                  dims=(), m_split: int = 1, name=None):
    """C = act(unpack(Ap) @ B + bias) with Ap (nm, nk, bm, bk) block-major.

    Every A DMA is one contiguous (bm*bk)-element block — no strided HBM
    reads, no relayout: the pre-pack payoff.  Epilogue fused into the
    final k step (see ``tsmm_tall_a``); ``m_split`` partitions the
    row-panel axis into per-core parallel chunks."""
    nm, nk, bm, bk = ap.shape
    k, n = b.shape
    assert k == nk * bk, (ap.shape, b.shape)
    grid, k_axis, row, default = _tall_grid(nm, nk, m_split)
    if row is None:
        in_specs = [pl.BlockSpec((1, 1, bm, bk), lambda i, j: (i, j, 0, 0)),
                    pl.BlockSpec((bk, n), lambda i, j: (j, 0))]
        o_spec = pl.BlockSpec((bm, n), lambda i, j: (i, 0))
        bias_spec = pl.BlockSpec((1, n), lambda i, j: (0, 0))
    else:
        in_specs = [pl.BlockSpec((1, 1, bm, bk),
                                 lambda p, i, j: (row(p, i), j, 0, 0)),
                    pl.BlockSpec((bk, n), lambda p, i, j: (j, 0))]
        o_spec = pl.BlockSpec((bm, n), lambda p, i, j: (row(p, i), 0))
        bias_spec = pl.BlockSpec((1, n), lambda p, i, j: (0, 0))
    args = [ap, b]
    if bias is not None:
        assert bias.shape == (n,), (bias.shape, n)
        in_specs.append(bias_spec)
        args.append(bias.reshape(1, n))
        kernel = functools.partial(_packed_a_kernel, nk=nk, k_axis=k_axis,
                                   act=act)
    else:
        kernel = functools.partial(_packed_a_kernel_nobias, nk=nk,
                                   k_axis=k_axis, act=act)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((nm * bm, n), b.dtype),
        scratch_shapes=[pltpu.VMEM((bm, n), jnp.float32)],
        compiler_params=_compiler_params(_semantics(dims, default)),
        interpret=interpret,
        name=name,
    )(*args)


# ---------------------------------------------------------------------------
# 2b. on-device pre-pack (the paper's PACKA as a kernel)
# ---------------------------------------------------------------------------


def _pack_kernel(a_ref, o_ref, *, alpha):
    blk = a_ref[...]
    if alpha != 1.0:
        blk = (blk.astype(jnp.float32) * alpha).astype(blk.dtype)
    o_ref[0, 0] = blk


def pack_blocks_kernel(a, bm: int, bk: int, *, alpha: float = 1.0,
                       interpret: bool = False):
    """(M, K) -> (nm, nk, bm, bk) block-major on-device re-tile.

    One grid step = one (bm x bk) tile read strided, written contiguous —
    the streaming layout transform the paper's pack module performs once
    per reused operand.  Requires M % bm == 0 and K % bk == 0 (ops.py pads).
    """
    m, k = a.shape
    assert m % bm == 0 and k % bk == 0, (a.shape, bm, bk)
    nm, nk = m // bm, k // bk
    return pl.pallas_call(
        functools.partial(_pack_kernel, alpha=alpha),
        grid=(nm, nk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, 1, bm, bk), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nm, nk, bm, bk), a.dtype),
        compiler_params=_compiler_params(("parallel", "parallel")),
        interpret=interpret,
    )(a)


# ---------------------------------------------------------------------------
# 2c. shared helpers for the generated variant kernels (kernels/gen.py —
#     the parameterized emitters the autotuner's grammar search lowers
#     through; DESIGN.md §14)
# ---------------------------------------------------------------------------


def _blk(ref, packed: bool):
    """A/W operand block: packed block-major refs carry (1, 1, b0, b1)."""
    return ref[0, 0] if packed else ref[...]


# ---------------------------------------------------------------------------
# 3. skinny-A x packed weight, fused epilogue (decode hot path)
# ---------------------------------------------------------------------------


def _skinny_a_kernel(x_ref, w_ref, bias_ref, o_ref, acc_ref, *, nk, act):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[0, 0], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(1) == nk - 1)
    def _done():
        o_ref[...] = _epilogue(acc_ref[...], bias_ref, act).astype(o_ref.dtype)


def _skinny_a_kernel_nobias(x_ref, w_ref, o_ref, acc_ref, *, nk, act):
    _skinny_a_kernel(x_ref, w_ref, None, o_ref, acc_ref, nk=nk, act=act)


def tsmm_skinny_a(x, wp, bias=None, *, act=None, interpret: bool = False,
                  dims=(), name=None, layer=None):
    """C = act(X @ unpack(Wp) + bias).

    X (m, K) with skinny m (decode batch); Wp (nk, nn, bk, bn) packed
    weights, or (L, nk, nn, bk, bn) with ``layer`` (see
    :func:`pallas_call`).  The whole X row-panel stays VMEM-resident
    across the grid (paper: the skinny operand is never split)."""
    m, k = x.shape
    nk, nn, bk, bn = wp.shape[-4:]
    assert k == nk * bk, (x.shape, wp.shape)
    n = nn * bn
    in_specs = [
        pl.BlockSpec((m, bk), lambda i, j: (0, j)),
        pl.BlockSpec((1, 1, bk, bn), lambda i, j: (j, i, 0, 0)),
    ]
    args = [x, wp]
    if bias is not None:
        assert bias.shape == (n,), (bias.shape, n)
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, i)))
        args.append(bias.reshape(1, n))
        kernel = functools.partial(_skinny_a_kernel, nk=nk, act=act)
    else:
        kernel = functools.partial(_skinny_a_kernel_nobias, nk=nk, act=act)
    return pallas_call(
        kernel,
        grid=(nn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=_compiler_params(
            _semantics(dims, ("parallel", "arbitrary"))),
        interpret=interpret,
        name=name,
        layer=layer,
    )(*args)
