"""Seeded weights that the benchmark makes, for the program and for the
reference alike.

Every value is a pure function of (seed, leaf path, layer, element
index): a 32-bit counter hash, mapped to a uniform variate with the
leaf's standard deviation and rounded once to the served dtype.  So the
program's whole tree comes out of one jitted call on the device, and the
reference regenerates any single layer on its own, bit for bit, without
taking anything that the program has made.

Scales follow the init the models use (``repro.models.param``, with
the experts' ``fan_in``): a weight has std ``1/sqrt(k)`` for its
contraction axis ``k`` (the second-to-last, so that an ``(E, k, n)``
expert stack is scaled by its own fan-in and not by its expert count),
the token table 0.02, a QKV bias 0.1 (so that the bias epilogue is
exercised), and every norm scale (``ln1``, ``ln2`` and any leaf whose
name ends in ``norm``: the final norm, MLA's ``q_norm``/``kv_norm``,
mamba2's gated norm) is 1.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

BIASES = ("bq", "bk", "bv")
EMBED_STD = 0.02
BIAS_STD = 0.1


def leaf_std(path: str, shape: tuple) -> float:
    """Standard deviation of a leaf by the last name of its path and its
    shape without the layer dim (0 for a norm scale, which is all ones).

    A leaf of rank 1 that is no norm or Q/K/V bias has ``1/sqrt(shape[0])``.
    The state-space leaves (``a_log``, ``dt_bias``, ``d_skip``, ``conv_w``,
    ``conv_b``) take these generic rules, not their model's init, until
    the first SSM configuration needs them."""
    name = path.rsplit("/", 1)[-1]
    if name in ("ln1", "ln2") or name.endswith("norm"):
        return 0.0
    if name == "tok":
        return EMBED_STD
    if name in BIASES:
        return BIAS_STD
    return 1.0 / math.sqrt(shape[-2] if len(shape) >= 2 else shape[0])


def _mix(x):
    """lowbias32 finalizer: a bijection of uint32 with full avalanche."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words (host side)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                      np.uint32)


def _leaf_key(words, path: str, layer):
    pid = jnp.uint32(zlib.crc32(path.encode()))
    k = _mix(jnp.asarray(layer, jnp.uint32) + jnp.uint32(0x9E3779B9))
    k = _mix(k ^ pid)
    k = _mix(k ^ words[1])
    return _mix(k ^ words[0])


def uniform_values(words, path: str, layer, flat_index, std: float):
    """f32 values with std ``std`` at the given flat element indices of
    one layer of one leaf.  ``layer`` and ``flat_index`` broadcast."""
    key = _leaf_key(words, path, layer)
    bits = _mix(_mix(flat_index.astype(jnp.uint32) + key))
    # a 24-bit signed integer is exact in f32, so one rounded multiply
    # makes each value: no fused or reordered arithmetic can differ
    # between the program's tree and the reference's layer
    centred = (bits >> 8).astype(jnp.int32) - jnp.int32(1 << 23)
    step = jnp.float32(std * math.sqrt(3.0) / (1 << 23))
    return centred.astype(jnp.float32) * step


def layer_leaf(words, path: str, layer, shape: tuple, dtype):
    """One layer's slice of a leaf (``shape`` without the layer dim)."""
    std = leaf_std(path, shape)
    if std == 0.0:
        return jnp.ones(shape, dtype)
    idx = jnp.arange(math.prod(shape), dtype=jnp.uint32).reshape(shape)
    return uniform_values(words, path, layer, idx, std).astype(dtype)


def rows_of(words, path: str, rows, width: int, dtype):
    """Rows ``rows`` (any int array) of an unstacked 2D leaf of ``width``
    columns, e.g. the token-table rows of a batch of ids."""
    std = leaf_std(path, (0, width))
    idx = (rows.astype(jnp.uint32)[..., None] * jnp.uint32(width)
           + jnp.arange(width, dtype=jnp.uint32))
    return uniform_values(words, path, 0, idx, std).astype(dtype)


def make_tree(words, leaves: dict):
    """Every leaf of a flat ``{path: (shape, dtype, stacked)}`` map;
    a stacked leaf has a leading layer dim.  Trace under ``jax.jit``."""
    out = {}
    for path, (shape, dtype, stacked) in leaves.items():
        if stacked:
            out[path] = jax.vmap(
                lambda l, p=path, s=shape[1:], dt=dtype:
                layer_leaf(words, p, l, s, dt))(
                jnp.arange(shape[0], dtype=jnp.uint32))
        else:
            out[path] = layer_leaf(words, path, 0, shape, dtype)
    return out
