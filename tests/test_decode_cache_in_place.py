"""The decode step reads its positional cache and its weights in place
(models/lm.py).

The layer scan walks the layer index alone; each layer reads its cache
slab from the loop-invariant stacked cache and returns only the token's
entries, (L, B, 1, ...).  One write per cache tensor lands after the
scan.  A cache passed through the scan as ``xs``/``ys`` is sliced in,
restacked and copied whole on every step, because a scan output cannot
alias a scanned input: these tests pin that it is not.  A packed weight
is read by its kernel at the layer index, with no slice made ahead of it.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models.registry import build_model

# dense MHA, GQA, MLA with an unscanned first dense layer, sliding window
ARCHS = ["qwen1_5_4b", "glm4_9b", "deepseek_v2_236b", "h2o_danube_1_8b"]
B, S = 2, 16


def _setup(arch):
    cfg = get_reduced_config(arch)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(B, S)
    tokens = jnp.ones((B, 1), jnp.int32)
    names = ("c", "kr") if cfg.use_mla else ("k", "v")
    return model, params, cache, tokens, [cache[n].shape for n in names]


def _scans(jaxpr):
    """Every scan equation in ``jaxpr``, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_scan_reads_cache_in_place(arch):
    model, params, cache, tokens, stacked = _setup(arch)
    closed = jax.make_jaxpr(model.decode_step)(params, cache, tokens)
    scans = list(_scans(closed.jaxpr))
    new = [sh[:2] + (1,) + sh[3:] for sh in stacked]
    # no scan carries, scans or stacks the cache ...
    for eqn in scans:
        moving = eqn.invars[eqn.params["num_consts"]:] + eqn.outvars
        assert not {v.aval.shape for v in moving} & set(stacked), eqn
    # ... the layer scan reads it as a loop invariant and returns only the
    # token's entries, one per layer
    (layer_scan,) = [e for e in scans
                     if {v.aval.shape for v in e.outvars} & set(new)]
    consts = layer_scan.invars[:layer_scan.params["num_consts"]]
    assert sorted(v.aval.shape for v in consts
                  if v.aval.shape in stacked) == sorted(stacked)
    assert sorted(v.aval.shape for v in layer_scan.outvars
                  if v.aval.shape in new) == sorted(new)


# MLA is left out: the reduced config scans one layer, whose bf16 c/kr
# stack the CPU's float normalisation copies; a compile for the TPU holds
# no copy, and neither does the CPU's with two scanned layers
@pytest.mark.parametrize("arch", [a for a in ARCHS if "deepseek" not in a])
def test_decode_program_holds_no_copy_of_the_cache(arch):
    model, params, cache, tokens, stacked = _setup(arch)
    fn = jax.jit(model.decode_step, donate_argnums=(1,))
    hlo = fn.lower(params, cache, tokens).compile().as_text()
    shape = re.escape("[" + ",".join(map(str, stacked[0])) + "]")
    assert re.search(rf"\w+{shape}\S* parameter\(", hlo)
    assert not re.search(rf"\w+{shape}\S* copy\(", hlo)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_writes_the_step_into_its_slot(arch):
    """Prefill s tokens then decode one: every cache tensor equals the
    cache a prefill of all s + 1 tokens leaves."""
    model, params, _, _, _ = _setup(arch)
    s = 10
    toks = (jnp.arange(B * (s + 1)).reshape(B, s + 1) * 5 + 3) % 97
    toks = toks.astype(jnp.int32)
    _, want = model.prefill(params, {"tokens": toks}, model.init_cache(B, S))
    _, got = model.prefill(params, {"tokens": toks[:, :s]},
                           model.init_cache(B, S))
    _, got = model.decode_step(params, got, toks[:, s:])
    assert set(got) == set(want)
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name], np.float32),
                                   np.asarray(want[name], np.float32),
                                   rtol=5e-2, atol=5e-2, err_msg=name)


def test_decode_kernels_read_stacked_weights_in_place(monkeypatch):
    """Every kernel in the layer scan takes its whole layer-stacked packed
    weight and the scan's layer index; the scan slices no weight."""
    from repro.serve.engine import pack_tree_for_serving
    monkeypatch.setenv("REPRO_TSMM_IMPL", "pallas_interpret")
    cfg = get_reduced_config("qwen1_5_4b").reduced(
        d_model=512, d_ff=1024, num_layers=2, vocab_size=1024,
        num_heads=8, num_kv_heads=8, head_dim=64)
    model = build_model(cfg)
    params, axes = model.init(jax.random.PRNGKey(0))
    packed, report = pack_tree_for_serving(params, axes, batch_m=B)
    stacked = {sh for path, sh in report.items() if path.startswith("layers")}
    assert len(stacked) >= 2 and all(len(sh) == 5 for sh in stacked)
    closed = jax.make_jaxpr(model.decode_step)(packed, model.init_cache(B, S),
                                               jnp.ones((B, 1), jnp.int32))
    (layer_scan,) = [e for e in _scans(closed.jaxpr)
                     if e.params["length"] == cfg.num_layers]
    xs = layer_scan.invars[layer_scan.params["num_consts"]
                           + layer_scan.params["num_carry"]:]
    assert [v.aval.shape for v in xs] == [(cfg.num_layers,)]
    calls = [e for e in layer_scan.params["jaxpr"].jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    calls += [e for e in _nested(layer_scan.params["jaxpr"].jaxpr)
              if e.primitive.name == "pallas_call"]
    assert calls
    for eqn in calls:
        shapes = [tuple(v.aval.shape) for v in eqn.invars]
        assert shapes[0] == (1,) and shapes[2] in stacked, shapes


def _nested(jaxpr):
    """Equations of every sub-jaxpr of ``jaxpr``'s equations."""
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from sub.eqns
            yield from _nested(sub)
