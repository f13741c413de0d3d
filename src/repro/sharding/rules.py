"""Logical-axis -> PartitionSpec rules.

The paper's *multi-thread optimizer* rule — never split the skinny dimension
of a TSMM across workers — generalizes here to the **skinny no-shard rule**:
an axis assignment is dropped whenever the dimension is smaller than
``SKINNY_MIN_PER_SHARD * axis_size`` or not divisible by the axis size.
That is exactly the paper's GEBB_t decision ("each core holds the whole B
block in its private L1") lifted to mesh axes: small dims are replicated so
every device holds the whole skinny operand, and parallelism comes from the
tall dimension only.

TP lives on the ``model`` axis, DP/FSDP on ``data`` (and ``pod`` when
present).  Rules return ``PartitionSpec`` trees mirroring the params tree.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.param import is_axes_leaf

# Logical axes that take the tensor-parallel ('model') axis.
TP_AXES = {"qheads", "kvheads", "mlp", "vocab", "experts", "ssm_inner", "ssm_heads"}
# Logical axes eligible for FSDP-style sharding on the data axis.
FSDP_AXES = {"embed"}
# Never sharded: per-head dims, scan dims, small structural dims.
NEVER = {"layers", "groups", "headdim", "state", "conv", "lora", "rope", "norm",
         "capacity", None}

# The skinny no-shard rule: require >= this many elements per shard.  8 is the
# f32 sublane tile; anything thinner than one tile per device round-trips
# through padding and (for TSMM operands) would defeat the whole point.
SKINNY_MIN_PER_SHARD = 8


@dataclasses.dataclass(frozen=True)
class ShardingOptions:
    tp_axis: str = "model"
    dp_axes: tuple = ("data",)            # ("pod","data") on the multi-pod mesh
    fsdp: bool = False                    # shard "embed" dims of params on dp
    fsdp_axes: tuple = ("data",)          # which dp axes FSDP uses
    # activation sequence sharding: False | True (dp axes) | "model"
    # ("model" = Megatron-SP: residual-stream seq over the TP axis)
    sequence_parallel: object = False
    # 2D weight-stationary tensor parallelism for serving: weights stay
    # sharded (rows on dp, cols on tp) and NEVER move; compute-path
    # activations are replicated over dp ("batch" unassigned) and the
    # packed-TSMM contraction k-shards over dp ("kblocks") with a psum of
    # the skinny output — the paper's "never move the tall operand" rule
    # at mesh scale.  KV caches keep their dp batch sharding (cache_batch).
    serve_2d_tp: bool = False


def axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, (tuple, list)):
        return int(np.prod([axis_size(mesh, n) for n in name]))
    return mesh.shape[name]


def _fits(dim: int, n_shards: int) -> bool:
    """Divisible and not skinny (the no-shard rule)."""
    return dim % n_shards == 0 and dim // n_shards >= SKINNY_MIN_PER_SHARD


def pspec_for(axes: tuple, shape: tuple, mesh: Mesh, opts: ShardingOptions) -> P:
    """PartitionSpec for one param leaf from its logical axes + shape."""
    assign: list = [None] * len(axes)
    used = set()
    # 1. tensor-parallel assignments
    for i, (ax, dim) in enumerate(zip(axes, shape)):
        if ax in TP_AXES and opts.tp_axis not in used and _fits(dim, axis_size(mesh, opts.tp_axis)):
            assign[i] = opts.tp_axis
            used.add(opts.tp_axis)
    # 2. FSDP on the remaining largest eligible dim
    if opts.fsdp:
        fs = tuple(a for a in opts.fsdp_axes if a not in used)
        if fs:
            n = axis_size(mesh, fs)
            cands = [
                (dim, i) for i, (ax, dim) in enumerate(zip(axes, shape))
                if assign[i] is None and ax in FSDP_AXES and _fits(dim, n)
            ]
            if cands:
                _, i = max(cands)
                assign[i] = fs if len(fs) > 1 else fs[0]
    return P(*assign)


def packed_pspec(axes: tuple, leaf, mesh: Mesh, opts: ShardingOptions) -> P:
    """Spec for a PackedTensor leaf: the logical (row, col) assignment moves
    to the block-count dims (n0, n1); block dims and lead dims replicate.
    The fit check runs on block counts (count per shard >= 1, divisible)."""
    blocks_shape = leaf.blocks.shape
    lead = len(blocks_shape) - 4
    n0, n1 = blocks_shape[lead], blocks_shape[lead + 1]
    row_ax, col_ax = axes[-2], axes[-1]
    assign = [None] * len(blocks_shape)
    used = set()
    for pos, (ax, cnt) in ((lead, (row_ax, n0)), (lead + 1, (col_ax, n1))):
        if ax in TP_AXES and opts.tp_axis not in used:
            n = axis_size(mesh, opts.tp_axis)
            if cnt % n == 0:
                assign[pos] = opts.tp_axis
                used.add(opts.tp_axis)
    if opts.fsdp:
        avail = tuple(a for a in opts.fsdp_axes if a not in used)
        # try the joint axes first, then single-axis subsets (multi-pod
        # meshes where the block count only divides one axis)
        for fs in (avail,) + tuple((a,) for a in avail):
            if not fs:
                continue
            n = axis_size(mesh, fs)
            done = False
            for pos, (ax, cnt) in ((lead, (row_ax, n0)),
                                   (lead + 1, (col_ax, n1))):
                if assign[pos] is None and ax in FSDP_AXES and cnt % n == 0:
                    assign[pos] = fs if len(fs) > 1 else fs[0]
                    done = True
                    break
            if done:
                break
    return P(*assign)


def param_pspecs(axes_tree, shapes_tree, mesh: Mesh, opts: ShardingOptions):
    """PartitionSpec tree for a params tree (arrays, ShapeDtypeStructs, or
    PackedTensor leaves).  ``axes_tree`` leads the traversal so packed
    leaves (which are themselves pytree nodes) are seen whole."""
    from repro.core.packing import is_packed

    def one(axes, leaf):
        if is_packed(leaf):
            return packed_pspec(axes, leaf, mesh, opts)
        return pspec_for(axes, leaf.shape, mesh, opts)

    return jax.tree.map(one, axes_tree, shapes_tree, is_leaf=is_axes_leaf)


def param_shardings(axes_tree, shapes_tree, mesh: Mesh, opts: ShardingOptions):
    specs = param_pspecs(axes_tree, shapes_tree, mesh, opts)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Cache specs (serving KV / SSM state placement)
# ---------------------------------------------------------------------------

# logical axes per decode-cache leaf (leading "dense{i}_" prefixes strip to
# the base name; hybrid stacks add a leading 'groups' dim).  Lives here —
# with the param rules — so the serving engine and the dry-run launcher
# place caches identically (DESIGN.md §13).
CACHE_AXES = {
    "pos": (),
    "slot_pos": (None,),
    # cache_seq: falls back to the model axis when kvheads can't take it
    # (GQA kv < tp) — the sequence-sharded KV cache for long-context decode.
    # cache_batch: dp-sharded even under serve_2d_tp (compute-path batch
    # replication must not blow up cache residency).
    "k": ("layers", "cache_batch", "cache_seq", "kvheads", "headdim"),
    "v": ("layers", "cache_batch", "cache_seq", "kvheads", "headdim"),
    "c": ("layers", "cache_batch", "cache_seq", "lora"),
    "kr": ("layers", "cache_batch", "cache_seq", "rope"),
    "ssm": ("layers", "cache_batch", "ssm_heads", "headdim", "state"),
    "conv": ("layers", "cache_batch", "conv", "ssm_inner"),
    "cross_k": ("layers", "cache_batch", "seq", "kvheads", "headdim"),
    "cross_v": ("layers", "cache_batch", "seq", "kvheads", "headdim"),
}


def cache_axes_for(cfg, key: str, ndim: int):
    base = key
    if key.startswith("dense") and "_" in key:
        base = key.split("_", 1)[1]
    ax = CACHE_AXES.get(base)
    if ax is None:
        return (None,) * ndim
    if len(ax) == ndim:
        return ax
    if len(ax) == ndim - 1:          # hybrid: extra leading 'groups' dim
        return ("groups",) + ax
    if len(ax) == ndim + 1:          # dense{i}_* lack the layer dim
        return ax[1:]
    return (None,) * ndim


def cache_pspecs(cfg, cache, mesh: Mesh, opts: ShardingOptions) -> dict:
    """PartitionSpec per decode-cache leaf (arrays or structs)."""
    from repro.sharding.context import ShardCtx  # lazy: context imports rules
    ctx = ShardCtx(mesh, opts)
    return {key: ctx.spec_for(cache_axes_for(cfg, key, leaf.ndim), leaf.shape)
            for key, leaf in cache.items()}


# ---------------------------------------------------------------------------
# Activation specs
# ---------------------------------------------------------------------------


def batch_pspec(global_batch: int, mesh: Mesh, opts: ShardingOptions) -> P:
    """Batch dim over the dp axes, honoring the skinny/divisibility rule
    (decode long_500k has batch=1 -> replicate)."""
    dp = tuple(a for a in opts.dp_axes if a in mesh.shape)
    n = axis_size(mesh, dp)
    if dp and global_batch % n == 0 and global_batch >= n:
        return P(dp if len(dp) > 1 else dp[0])
    # try a prefix of the dp axes (e.g. batch 32 on a 2x16x16 mesh: use pod x data = 32)
    for k in range(len(dp), 0, -1):
        sub = dp[:k]
        n = axis_size(mesh, sub)
        if global_batch % n == 0 and global_batch >= n:
            return P(sub if len(sub) > 1 else sub[0])
    return P(None)


def tokens_pspec(global_batch: int, seq: int, mesh: Mesh, opts: ShardingOptions) -> P:
    b = batch_pspec(global_batch, mesh, opts)
    if opts.sequence_parallel and b == P(None):
        # batch unshardable (e.g. long-context batch=1): shard seq on data
        dp = tuple(a for a in opts.dp_axes if a in mesh.shape)
        n = axis_size(mesh, dp)
        if seq % n == 0:
            return P(None, dp if len(dp) > 1 else dp[0])
    return P(*b, None)


def constraint(x, spec: P):
    """with_sharding_constraint that is a no-op outside a mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x
