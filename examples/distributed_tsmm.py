import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Distributed TSMM demo (8 virtual devices): the paper's multi-thread
optimizer at mesh scale.

    PYTHONPATH=src python examples/distributed_tsmm.py

Compares three decompositions of the same tall-and-skinny matmul:
  1. distributed_tsmm   — shard the TALL dim, replicate skinny B
                          (AutoTSMM rule: ZERO collectives)
  2. conventional_ksplit — split the contraction dim + all-reduce
                          (what a generic library does)
  3. overlapped_ring    — beyond-paper: ppermute pipeline when A arrives
                          k-sharded from an upstream TP layer
and counts the collective ops each one compiles to.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tsmm as T
from repro.kernels import ref
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
a = jnp.asarray(rng.standard_normal((4096, 2048)), jnp.float32)
b = jnp.asarray(rng.standard_normal((2048, 16)), jnp.float32)
want = ref.tsmm_ref(a, b)

for name, fn in [
    ("distributed_tsmm (m-split)", lambda x, y: T.distributed_tsmm(x, y, mesh, "data")),
    ("conventional_ksplit", lambda x, y: T.conventional_ksplit(x, y, mesh, "data")),
    ("overlapped_ring", lambda x, y: T.overlapped_ring_tsmm(x, y, mesh, "data")),
]:
    got = fn(a, b)
    err = float(jnp.abs(got - want).max())
    hlo = jax.jit(fn).lower(a, b).compile().as_text()
    colls = {op: len(re.findall(op, hlo))
             for op in ("all-reduce", "all-gather", "collective-permute")}
    colls = {k: v for k, v in colls.items() if v}
    print(f"{name:28s} err={err:.2e} collectives={colls or 'NONE'}")
