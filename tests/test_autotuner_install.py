"""Install-time stage: CLI problem enumeration + plan registry behaviour."""

import jax
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.core import registry
from repro.core.install import serving_problems
from repro.core.plan import is_tsmm


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_serving_problems_are_tsmm(arch):
    probs = serving_problems(get_config(arch))
    assert probs, arch
    for p in probs:
        assert is_tsmm(p.m, p.k, p.n)
        assert p.skinny <= 256


def test_registry_persists_across_clear(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans.json"))
    registry.clear_memory()
    from repro.core.autotuner import make_plan
    from repro.core.plan import Problem
    p1 = make_plan(Problem(8192, 4096, 16, "float32"))
    registry.clear_memory()          # drop memory; file must survive
    p2 = make_plan(Problem(8192, 4096, 16, "float32"))
    assert p1 == p2
    registry.clear_memory()


def test_planning_spec_follows_device_kind(monkeypatch):
    """A TPU plans against its own ``device_kind``'s spec and an unknown
    kind is an error; every other backend plans against the v5e model.
    The VMEM limit every kernel compiles with stays below the chip's
    physical VMEM."""
    from repro.core import autotuner
    from repro.core.hw import (TPU_V5E, VMEM_LIMIT_BYTES,
                               spec_for_device_kind)
    assert spec_for_device_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="no hardware spec"):
        spec_for_device_kind("TPU v99")
    assert VMEM_LIMIT_BYTES < TPU_V5E.vmem_bytes

    class FakeDevice:
        device_kind = "TPU v99"

    monkeypatch.setattr(autotuner, "_DEFAULT_HW", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeDevice()])
    with pytest.raises(ValueError, match="TPU v99"):
        autotuner.default_hw()
    monkeypatch.setattr(autotuner, "_DEFAULT_HW", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert autotuner.default_hw() is TPU_V5E
