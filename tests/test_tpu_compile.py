"""Deviceless compiles for a described TPU v5e (``v5e:2x2`` topology).

The TPU compiler is installed even where no chip is attached, so the main
path's Pallas kernels are compiled here at Qwen1.5-4B widths (d_model
2560, d_ff 6912, 20 heads of 128).  A compile refuses what interpret mode
cannot see: a scoped-VMEM allocation over the limit, a slice not aligned
to the tiling.  Nothing runs, so these tests say nothing about results or
times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and pytest-xdist workers import every test file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.autotuner import candidate_blocks
from repro.core.hw import MiB, VMEM_LIMIT_BYTES
from repro.core.plan import Problem
from repro.core.vmem_model import vmem_bytes_needed
from repro.kernels import variants
from repro.kernels.flash_attention import flash_attention

D_MODEL, D_FF, VOCAB = 2560, 6912, 151936
SHAPES = [(D_MODEL, D_MODEL), (D_MODEL, D_FF), (D_FF, D_MODEL),
          (D_MODEL, VOCAB)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_plan(plan, sharding):
    """Compile the plan's Pallas program exactly as ``tsmm_dot`` replays
    it (packed operand, the plan's grammar point and schedule)."""
    p = plan.problem
    dt = jnp.bfloat16
    if plan.orientation == "skinny_a":
        nk, nn = -(-p.k // plan.bk), -(-p.n // plan.bn)
        x = _struct((p.m, p.k), dt, sharding)
        wp = _struct((nk, nn, plan.bk, plan.bn), dt, sharding)
        fn = lambda x, w: variants.run_skinny_a(  # noqa: E731
            plan.kernel, x, w, bk=plan.bk, bn=plan.bn, packed=True,
            impl="pallas", schedule=plan.schedule)
        args = (x, wp)
    else:
        nm, nk = -(-p.m // plan.bm), -(-p.k // plan.bk)
        ap = _struct((nm, nk, plan.bm, plan.bk), dt, sharding)
        b = _struct((p.k, p.n), dt, sharding)
        fn = lambda a, b: variants.run_tall_a(  # noqa: E731
            plan.kernel, a, b, bm=plan.bm, bk=plan.bk, packed=True,
            impl="pallas", schedule=plan.schedule)
        args = (ap, b)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _best(problem):
    """The planner's model-ranked plan (no registry involved)."""
    return candidate_blocks(problem)[0]


def test_decode_skinny_plan_compiles(one_chip):
    plan = _best(Problem(8, D_MODEL, D_FF))
    assert plan.orientation == "skinny_a"
    _compile_plan(plan, one_chip)


def test_prefill_plan_m2048_compiles(one_chip):
    """Under Mosaic's 16 MiB default this plan is refused (an 18 MiB
    scoped allocation); it compiles with the planner's own limit."""
    plan = _best(Problem(2048, D_MODEL, D_FF))
    assert vmem_bytes_needed(plan) > 16 * MiB
    _compile_plan(plan, one_chip)


def test_largest_admitted_plan_compiles(one_chip):
    """The feasibility gate's promise at qwen widths: the candidate with
    the largest modelled working set still compiles under the limit."""
    cands = [c for m in (8, 256, 2048) for (k, n) in SHAPES
             for c in candidate_blocks(Problem(m, k, n))]
    plan = max(cands, key=vmem_bytes_needed)
    assert 32 * MiB < vmem_bytes_needed(plan) <= VMEM_LIMIT_BYTES
    _compile_plan(plan, one_chip)


def test_flash_attention_head_dim_128_compiles(one_chip):
    q = _struct((1, 20, 512, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(flash_attention).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_planned_kernel_named_after_its_leaf(one_chip, monkeypatch):
    """``linear(..., name=leaf)`` lowers the packed weight's kernel as a
    ``tsmm_<leaf>`` custom call under the ``<leaf>`` scope, so the
    profiler names the kernel by the weight it reads."""
    from repro.core.linear import linear
    from repro.core.packing import pack

    monkeypatch.setenv("REPRO_TSMM_IMPL", "pallas")
    plan = _best(Problem(16, D_FF, D_MODEL))
    w = jax.ShapeDtypeStruct((D_FF, D_MODEL), jnp.bfloat16)
    packed = jax.eval_shape(lambda w: pack(w, plan.bk, plan.bn), w)
    packed = jax.tree.map(
        lambda s: _struct(s.shape, s.dtype, one_chip), packed)
    x = _struct((16, D_FF), jnp.bfloat16, one_chip)
    hlo = jax.jit(lambda x, w: linear(x, w, name="w_down")).lower(
        x, packed).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(re.search(r"%tsmm_w_down(\.\d+)? = ", line)
                         for line in calls)
    assert all("/w_down/" in line for line in calls)


@pytest.mark.parametrize("spec", [None, "ksplit:splits=2"])
def test_layer_view_kernel_reads_stacked_weight(one_chip, monkeypatch, spec):
    """A layer view's kernel (``PackedTensor.at_layer`` in a scan over the
    layer index) takes the whole layer-stacked weight: the program holds
    no slice of one layer's blocks ahead of the kernel."""
    from repro.core.linear import linear
    from repro.core.packing import pack

    monkeypatch.setenv("REPRO_TSMM_IMPL", "pallas")
    if spec is not None:
        monkeypatch.setenv("REPRO_TSMM_VARIANT", spec)
    plan = _best(Problem(16, D_MODEL, D_FF))
    w = jax.ShapeDtypeStruct((3, D_MODEL, D_FF), jnp.bfloat16)
    packed = jax.eval_shape(lambda w: pack(w, plan.bk, plan.bn), w)
    packed = jax.tree.map(
        lambda s: _struct(s.shape, s.dtype, one_chip), packed)
    x = _struct((16, D_MODEL), jnp.bfloat16, one_chip)

    def f(x, w):
        def body(xc, i):
            h = linear(xc, w.at_layer(i), name="w_gate")
            return xc + h[:, :D_MODEL], None
        return jax.lax.scan(body, x, jnp.arange(3))[0]

    hlo = jax.jit(f).lower(x, packed).compile().as_text()
    stacked = "bf16[" + ",".join(map(str, packed.blocks.shape)) + "]"
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(stacked in line.split("operand_layout")[-1]
                         for line in calls)
    one = "bf16[" + ",".join(map(str, packed.blocks.shape[1:])) + "]"
    assert not any(re.search(rf"= {re.escape(one)}\S* (fusion|dynamic-slice)\(",
                             line) for line in hlo.splitlines())
