"""``bench/counts.py`` against hand counts: both dense configurations at
their published widths, where every count is as the benchmark's first,
dense-only version gave it, and the reduced DeepSeek-V2 (latent
attention, routed and shared experts, a leading dense layer)."""

import dataclasses
import json
from pathlib import Path

import pytest

from bench import counts

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
DATA = Path(__file__).resolve().parent / "data"
DENSE = ["qwen1.5-4b", "glm4-9b-l20"]


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def model(name):
    return config(name)["model"]


def deepseek(**over):
    """The reduced DeepSeek-V2 as a model block: d 128, 4 heads, nope 32,
    rope 16, value 32, kv LoRA 32, q LoRA 48, 2 layers (the first dense,
    d_ff 256), 8 experts top-2 of width 64, 1 shared, vocabulary 512."""
    from repro.configs.deepseek_v2_236b import REDUCED
    return dict(dataclasses.asdict(REDUCED), **over)


# per layer: attention wq_a 128x48, wq_b 48x(4 x 48), wkv_a 128x(32 + 16),
# wkv_b 32x(4 x 64), wo (4 x 32)x128
DS_ATTN = 128 * 48 + 48 * 192 + 128 * 48 + 32 * 256 + 128 * 128
DS_DENSE_MLP = 3 * 128 * 256
DS_ROUTER, DS_SHARED, DS_EXPERT = 128 * 8, 3 * 128 * 64, 3 * 128 * 64
DS_HEAD = 128 * 512


# hand counts: per layer 4 x 2560^2 + 3 x 2560 x 6912, 40 layers, and the
# 2560 x 151936 head; GLM: 2 x 4096^2 + 2 x 4096 x 256 + 3 x 4096 x 13696
# per layer, 20 layers, and the 4096 x 151552 head; DeepSeek: two latent
# attentions, the dense MLP, then the router, the shared expert and, of
# the 8 held experts, the 2 a token uses; with 32 experts published and 8
# held, a token reaches 2 x 8 / 32 of them
SPECS = {"deepseek-reduced": deepseek, "deepseek-held-8":
         lambda: deepseek(num_experts_total=32)}


@pytest.mark.parametrize("name,params", [
    ("qwen1.5-4b", 40 * (4 * 2560 ** 2 + 3 * 2560 * 6912) + 2560 * 151936),
    ("glm4-9b-l20", 20 * (2 * 4096 ** 2 + 2 * 4096 * 256
                          + 3 * 4096 * 13696) + 4096 * 151552),
    ("deepseek-reduced", 2 * DS_ATTN + DS_DENSE_MLP + DS_ROUTER + DS_SHARED
     + 2 * DS_EXPERT + DS_HEAD),
    ("deepseek-held-8", 2 * DS_ATTN + DS_DENSE_MLP + 128 * 32 + DS_SHARED
     + 0.5 * DS_EXPERT + DS_HEAD),
])
def test_matmul_params(name, params):
    spec = SPECS[name]() if name in SPECS else model(name)
    assert counts.matmul_params(spec) == params


def test_matmul_flops_and_bytes():
    c = counts.Matmul("w", m=4, k=2560, n=6912)
    assert c.flops == 2 * 4 * 2560 * 6912
    assert c.bytes == 2 * (2560 * 6912 + 4 * 2560 + 4 * 6912)
    b = counts.Matmul("wq", m=4, k=2560, n=2560, bias=True)
    assert b.bytes == 2 * (2560 * 2560 + 4 * 2560 + 4 * 2560 + 2560)
    r = counts.Matmul("w_up.1", m=3, k=128, n=64, routed=True, weights=2.5)
    assert r.leaf == "w_up"
    assert r.bytes == 2 * (2.5 * 128 * 64 + 3 * 128 + 3 * 64)


def test_decode_step_is_memory_bound_at_weight_stream():
    peak = counts.peaks("TPU v5 lite")
    m = model("qwen1.5-4b")
    calls = counts.step_matmuls(m, 4)
    assert len(calls) == 40 * 7 + 1
    t, bound = counts.least_time(calls, peak)
    assert bound == "memory"
    weights = 2 * counts.matmul_params(m)           # bf16, read once
    assert weights / 819e9 < t < 1.01 * weights / 819e9


def _width_filter(calls, hlo: str) -> list:
    """The matmuls whose output width is that of some kernel in ``hlo``:
    how the benchmark's first version matched kernels to matmuls."""
    import re
    widths = {int(m.group(1).split(",")[-1]) for line in hlo.splitlines()
              if 'custom_call_target="tpu_custom_call"' in line
              for m in [re.search(r"=\s*\(?\s*\w+\[([\d,]*)\]", line)] if m}
    return [c for c in calls if c.n in widths]


ALL_LEAVES = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "head"}
# the kernel lines of every served program, as a v5e compiled them
KERNELS = json.loads((DATA / "kernels_v5e.json").read_text())


def test_kernel_filter_by_output_width():
    """Matched by leaf name, each served program's kernel matmuls are the
    ones matched by output width, on the HLO the chip compiled: every
    projection and the head, less GLM's ``wk``/``wv`` in prefill, which
    run outside the kernels there."""
    from bench import run as R
    for name in DENSE:
        m = model(name)
        for program, lines in KERNELS[name].items():
            hlo = "\n".join(lines)
            n, got = R.kernel_leaves(hlo)
            leaves = (ALL_LEAVES - {"wk", "wv"}
                      if (name, program[:7]) == ("glm4-9b-l20", "prefill")
                      else ALL_LEAVES)
            assert got == leaves, (name, program)
            assert n == len(leaves)
            rows = (config(name)["engine"]["slots"]
                    if program == "decode_step"
                    else int(program.split(".")[1]))
            every = counts.step_matmuls(m, rows)
            kept = counts.step_matmuls(m, rows, got)
            assert kept == _width_filter(every, hlo)
            assert len(every) - len(kept) == (
                0 if leaves == ALL_LEAVES else 2 * m["num_layers"])


def _parent_linears(m):
    """The dense layer's projections, as the first version listed them."""
    d, h, kh, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    b = bool(m["qkv_bias"])
    return [("wq", d, h * hd, b), ("wk", d, kh * hd, b),
            ("wv", d, kh * hd, b), ("wo", h * hd, d, False),
            ("w_gate", d, ff, False), ("w_up", d, ff, False),
            ("w_down", ff, d, False)]


def test_prefill_flops_hand_count():
    """Every count of a dense configuration is, float for float, the
    first version's: parameters, a token's and a prompt's FLOPs, and the
    step's matmuls."""
    for name in DENSE:
        m = model(name)
        lin = _parent_linears(m)
        layers, h, hd = m["num_layers"], m["num_heads"], m["head_dim"]
        params = (layers * sum(k * n for _, k, n, _ in lin)
                  + m["d_model"] * m["vocab_size"])
        assert counts.matmul_params(m) == params
        assert type(counts.matmul_params(m)) is int
        for ctx in (1, 10, 2047):
            assert counts.token_flops(m, ctx) == (
                2.0 * params + 4.0 * layers * h * hd * ctx)
        for p in (1, 100, 1023):
            assert counts.prompt_flops(m, p) == (2.0 * params * p + 4.0
                                                 * layers * h * hd
                                                 * (p * (p + 1) / 2))
        for rows in (4, 16, 512):
            want = [counts.Matmul(f"{n}.{i}", rows, k, w, b) for i in
                    range(layers) for n, k, w, b in lin]
            want.append(counts.Matmul("head", rows, m["d_model"],
                                      m["vocab_size"]))
            assert counts.step_matmuls(m, rows) == want


def test_latent_attention_and_experts_hand_count():
    m = deepseek()
    attn = [("wq_a", 128, 48), ("wq_b", 48, 192), ("wkv_a", 128, 48),
            ("wkv_b", 32, 256), ("wo", 128, 128)]
    assert counts.layer_linears(m, 0) == [
        (n, k, w, False, 0) for n, k, w in attn] + [
        ("w_gate", 128, 256, False, 0), ("w_up", 128, 256, False, 0),
        ("w_down", 256, 128, False, 0)]
    assert counts.layer_linears(m, 1) == [
        (n, k, w, False, 0) for n, k, w in attn] + [
        ("router", 128, 8, False, 0), ("ws_gate", 128, 64, False, 0),
        ("ws_up", 128, 64, False, 0), ("ws_down", 64, 128, False, 0),
        ("w_gate", 128, 64, False, 8), ("w_up", 128, 64, False, 8),
        ("w_down", 64, 128, False, 8)]
    # no Q LoRA: one direct projection to every head's nope and rope dims
    direct = counts.layer_linears(deepseek(q_lora_rank=0), 1)
    assert direct[0] == ("wq", 128, 4 * 48, False, 0)
    assert [c[0] for c in direct[:4]] == ["wq", "wkv_a", "wkv_b", "wo"]
    # attention per pair of positions: 2 h (dn + dr) + 2 h dv, each layer
    params = counts.matmul_params(m)
    pair = 2 * 4 * (32 + 16) + 2 * 4 * 32
    assert counts.token_flops(m, 10) == 2.0 * params + 2 * pair * 10
    assert counts.prompt_flops(m, 7) == 2.0 * params * 7 + 2 * pair * 28


def test_routed_experts_never_pass_the_kernel_filter():
    m = deepseek(num_experts_total=32)
    calls = counts.step_matmuls(m, 16)
    routed = [c for c in calls if c.routed]
    assert [c.name for c in routed] == ["w_gate.1", "w_up.1", "w_down.1"]
    # 16 tokens x 2 experts each, a quarter of them on the 8 held here;
    # an expert is missed by all 16 with probability (1 - 2/32)^16
    for c in routed:
        assert c.m == 16 * 2 * 8 / 32
        assert c.weights == pytest.approx(8 * (1 - (30 / 32) ** 16))
    leaves = {"wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up",
              "w_down", "ws_gate", "ws_up", "ws_down", "head"}
    kept = counts.step_matmuls(m, 16, leaves)
    assert not any(c.routed for c in kept)
    assert {c.name for c in kept} == {c.name for c in calls
                                      if not c.routed and c.leaf != "router"}


def test_weight_params_sum_the_matmul_leaves_of_the_tree():
    """Every weight a matmul reads, each held expert once: the sizes of
    the program's leaves of rank 2 or more, less the token table (a
    lookup)."""
    import math
    from bench import run as R
    from repro.configs.deepseek_v2_236b import REDUCED
    from repro.models.registry import build_model
    leaves, _ = R.param_leaves(build_model(REDUCED))
    want = sum(math.prod(shape) for p, (shape, _, stacked) in leaves.items()
               if len(shape) - stacked >= 2 and p != "embed/tok")
    assert counts.weight_params(deepseek()) == want
    assert want == (2 * DS_ATTN + DS_DENSE_MLP + DS_ROUTER + DS_SHARED
                    + 8 * DS_EXPERT + DS_HEAD)


def test_families_without_counts_are_an_error():
    with pytest.raises(ValueError):
        counts.matmul_params(dict(model("qwen1.5-4b"), family="ssm"))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("TPU v4")
