"""The DeepSeek-V2-Lite expert-parallel share and the document-QA cell:
both load through the harness, the configuration builds the program's
model, and ``bench/counts.py`` counts the program's own matmul leaves."""

import json
from pathlib import Path

import pytest

from bench import counts
from bench import run as R
from bench import traffic

ROOT = Path(__file__).resolve().parents[2]
CELLS = {"deepseek-v2-lite-ep8.batch": ("deepseek-v2-lite-ep8",
                                        "batch_gen_wide", ".dsv2"),
         "qwen1.5-4b.docqa": ("qwen1.5-4b", "docqa", ".docqa")}
DSV2 = ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8.json"


def _model():
    return json.loads(DSV2.read_text())["model"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_loads(name):
    config, mix, suffix = CELLS[name]
    cell = R.load_cell(name)
    assert cell.chips == 1 and cell.config["name"] == config
    assert cell.mix == traffic.load_mix(mix)
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names and all(n.endswith(suffix) for n in names)
    for n in names:                 # every reader is found by the suffix
        assert hasattr(R.load_metric(n), "read")
    lens = traffic.length_set(cell.mix["prompt_tokens"],
                              cell.mix["block"])
    assert lens.max() <= cell.config["engine"]["max_prompt"]
    assert (cell.mix["prompt_tokens"]["max"] + cell.mix["output_tokens"]
            ["max"] <= cell.config["engine"]["max_len"])


def test_deepseek_model_block_builds_the_share():
    from repro.configs.base import ModelConfig
    from repro.configs.deepseek_v2_lite import CONFIG
    cfg = ModelConfig(**_model())
    assert (cfg.num_experts, cfg.num_experts_total) == (8, 64)
    # every published width of the catalog config, 8 experts held
    spec = json.loads(DSV2.read_text())
    assert cfg.d_model == spec["hidden_size"]
    assert cfg.d_ff_expert == spec["moe_intermediate_size"]
    assert cfg.num_experts_total == spec["n_routed_experts"]
    for field in ("num_layers", "d_model", "num_heads", "head_dim", "d_ff",
                  "vocab_size", "num_shared_experts", "experts_per_token",
                  "d_ff_expert", "norm_topk_prob", "first_k_dense",
                  "kv_lora_rank", "q_lora_rank", "rope_head_dim",
                  "v_head_dim", "rope_theta", "yarn_factor",
                  "yarn_mscale", "yarn_mscale_all_dim", "norm_eps"):
        assert getattr(cfg, field) == getattr(CONFIG, field), field


def test_counts_name_the_program_leaves():
    """Per layer, the matmul leaves ``layer_linears`` counts are the
    program's weight leaves of rank 2 or more (a router, attention and
    shared-expert matrices, the (E, k, n) expert stacks), by name and
    shape."""
    from repro.configs.base import ModelConfig
    from repro.models.registry import build_model
    m = _model()
    leaves, _ = R.param_leaves(build_model(ModelConfig(**m)))
    for layer, prefix in ((0, "dense0/"), (1, "layers/")):
        want = {}
        for path, (shape, _, stacked) in leaves.items():
            if path.startswith(prefix) and len(shape) - stacked >= 2:
                want[path.rsplit("/", 1)[-1]] = tuple(shape[stacked:])
        got = {name: ((e, k, n) if e else (k, n))
               for name, k, n, _, e in counts.layer_linears(m, layer)}
        assert got == want, layer


def test_parameter_counts():
    """1.270 B weights multiplied per token: 1.10 B outside the routed
    experts and 6/64 of the held experts' 1.80 B; 2.901 B held: the
    3.11 B of the share less the 0.21 B token table."""
    m = _model()
    assert round(counts.matmul_params(m) / 1e9, 3) == 1.270
    assert round(counts.weight_params(m) / 1e9, 3) == 2.901
    routed = 26 * 8 * 3 * 2048 * 1408
    assert round(routed / 1e9, 2) == 1.80
    assert counts.matmul_params(m) == pytest.approx(
        counts.weight_params(m) - routed * (1 - 6 / 64))
