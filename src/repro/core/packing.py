"""The pre-pack module: persistent block-major weight layout.

``PackedTensor`` is a registered pytree, so packed weights live inside the
params tree, flow through ``jax.jit`` / ``lax.scan`` / checkpointing like
any array, and are packed ONCE at load time — the paper's 'pack to a
permanent memory address, reuse across calls'.

Packing supports leading batch dims (stacked scan layers pack per-layer),
folds the alpha scale like the paper's PACKA, and zero-pads to block
multiples (so downstream kernels never see ragged edges).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedTensor:
    """Block-major packed 2D weight (possibly with leading stack dims).

    blocks: (*lead, n0, n1, b0, b1) where the original matrix is
    (*lead, n0*b0 - pad0, n1*b1 - pad1).

    ``kernel_specs`` is the serving-replay stamp (DESIGN.md §10): sorted
    ``(batch_bucket, KernelSpec)`` pairs recording which inner-kernel
    variant the autotuner chose per bucket when this weight was packed
    (``core.tsmm.prepack_for``).  It rides in the pytree aux (static,
    hashable), so the decode path replays the recorded variant without
    re-deriving the registry key — which a sharded engine could not do
    (its plans are keyed by per-shard dims and num_shards).  Empty for
    manually packed tensors.

    ``shard_axes`` is the (row-block, column-block) mesh axis the serving
    engine shards the block-count dims over ((None, None) off-mesh).  A
    Mosaic kernel cannot be partitioned by XLA, so ``tsmm_dot`` reads it
    to run one kernel per shard.

    ``layer`` (an int32 scalar, usually traced) makes the tensor one
    layer's view of layer-stacked ``blocks`` (L, n0, n1, b0, b1) without
    slicing them: the skinny kernels read row ``layer`` themselves
    (``kernels.tsmm.pallas_call``).  None for an ordinary tensor.
    """

    blocks: jnp.ndarray
    orig_rows: int      # pre-padding
    orig_cols: int
    kernel_specs: tuple = ()
    shard_axes: tuple = (None, None)
    layer: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        aux = (self.orig_rows, self.orig_cols, self.kernel_specs,
               self.shard_axes)
        if self.layer is None:
            return (self.blocks,), aux
        return (self.blocks, self.layer), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux, *children[1:])

    def at_layer(self, i) -> "PackedTensor":
        """Layer ``i``'s view of a tensor stacked over one leading layer
        dim (see ``layer``)."""
        assert self.layer is None and self.blocks.ndim == 5, \
            self.blocks.shape
        return dataclasses.replace(self, layer=jnp.asarray(i, jnp.int32))

    # -- convenience ---------------------------------------------------
    @property
    def block_shape(self):
        return self.blocks.shape[-2:]

    @property
    def lead_shape(self):
        return self.blocks.shape[:-4] if self.layer is None else \
            self.blocks.shape[1:-4]

    @property
    def shape(self):
        """Logical (unpacked, unpadded) shape."""
        return (*self.lead_shape, self.orig_rows, self.orig_cols)

    @property
    def dtype(self):
        return self.blocks.dtype

    @property
    def ndim(self):
        return len(self.shape)

    def unpack(self) -> jnp.ndarray:
        f = lambda bl: ops.unpack_blocks(bl, self.orig_rows, self.orig_cols)
        for _ in self.lead_shape:
            f = jax.vmap(f)
        return f(self.blocks if self.layer is None
                 else self.blocks[self.layer])


def pack(w, b0: int, b1: int, alpha: float = 1.0) -> PackedTensor:
    """Pack the trailing 2 dims of ``w`` into (n0, n1, b0, b1) blocks."""
    lead = w.shape[:-2]
    rows, cols = w.shape[-2:]
    f = lambda x: ops.pack_blocks(x, b0, b1, alpha)
    for _ in lead:
        f = jax.vmap(f)
    return PackedTensor(f(w), rows, cols)


def is_packed(x) -> bool:
    return isinstance(x, PackedTensor)


# ---------------------------------------------------------------------------
# Serving-time pre-pack policy
# ---------------------------------------------------------------------------

# A weight leaf is worth pre-packing for decode if its trailing dims form a
# big-by-big matrix that a skinny activation panel will hit.
MIN_PACK_DIM = 1024


def pack_params_for_serving(params, axes, *, bk: int = 512, bn: int = 512,
                            predicate=None):
    """Replace eligible 2D weight leaves with PackedTensor.

    ``axes`` is the logical-axes tree (same structure).  Default policy:
    pack leaves whose last two dims are both >= MIN_PACK_DIM and whose
    logical axes mark a contraction->output pair (first of the two is the
    activation-contracted dim).  Returns (packed_params, n_packed).
    """
    count = [0]

    def _one(leaf, ax):
        if predicate is not None and not predicate(leaf, ax):
            return leaf
        if not hasattr(leaf, "ndim") or leaf.ndim < 2:
            return leaf
        r, c = leaf.shape[-2:]
        if r >= MIN_PACK_DIM and c >= MIN_PACK_DIM:
            count[0] += 1
            return pack(leaf, min(bk, r), min(bn, c))
        return leaf

    from repro.models.param import is_axes_leaf
    packed = jax.tree.map(_one, params, axes,
                          is_leaf=lambda x: is_axes_leaf(x) or not isinstance(x, dict))
    return packed, count[0]
