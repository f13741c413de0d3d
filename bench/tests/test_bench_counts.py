"""``bench/counts.py`` against hand counts at both configurations' widths."""

import json
from pathlib import Path

import pytest

from bench import counts

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


# hand counts: per layer 4 x 2560^2 + 3 x 2560 x 6912, 40 layers, and the
# 2560 x 151936 head; GLM: 2 x 4096^2 + 2 x 4096 x 256 + 3 x 4096 x 13696
# per layer, 20 layers, and the 4096 x 151552 head
@pytest.mark.parametrize("name,params", [
    ("qwen1.5-4b", 40 * (4 * 2560 ** 2 + 3 * 2560 * 6912) + 2560 * 151936),
    ("glm4-9b-l20", 20 * (2 * 4096 ** 2 + 2 * 4096 * 256
                          + 3 * 4096 * 13696) + 4096 * 151552),
])
def test_matmul_params(name, params):
    assert counts.matmul_params(model(name)) == params


def test_matmul_flops_and_bytes():
    c = counts.Matmul("w", m=4, k=2560, n=6912)
    assert c.flops == 2 * 4 * 2560 * 6912
    assert c.bytes == 2 * (2560 * 6912 + 4 * 2560 + 4 * 6912)
    b = counts.Matmul("wq", m=4, k=2560, n=2560, bias=True)
    assert b.bytes == 2 * (2560 * 2560 + 4 * 2560 + 4 * 2560 + 2560)


def test_decode_step_is_memory_bound_at_weight_stream():
    peak = counts.peaks("TPU v5 lite")
    m = model("qwen1.5-4b")
    calls = counts.step_matmuls(m, 4)
    assert len(calls) == 40 * 7 + 1
    t, bound = counts.least_time(calls, peak)
    assert bound == "memory"
    weights = 2 * counts.matmul_params(m)           # bf16, read once
    assert weights / 819e9 < t < 1.01 * weights / 819e9


def test_kernel_filter_by_output_width():
    m = model("glm4-9b-l20")
    all_calls = counts.step_matmuls(m, 512)
    no_kv = counts.step_matmuls(m, 512, {4096, 13696, 151552})
    assert len(all_calls) - len(no_kv) == 2 * 20     # wk and wv per layer


def test_prefill_flops_hand_count():
    m = model("qwen1.5-4b")
    p = 100
    attn = 4 * 40 * 20 * 128 * (p * (p + 1) / 2)
    assert counts.prompt_flops(m, p) == 2 * counts.matmul_params(m) * p + attn
    assert counts.token_flops(m, 10) == (2 * counts.matmul_params(m)
                                         + 4 * 40 * 20 * 128 * 10)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("TPU v4")
