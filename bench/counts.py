"""Operations and HBM bytes of the dense LM's work, from shapes alone.

Algorithmic counts, the same whatever plan, block shape or kernel runs a
matmul: a call of ``m`` rows against a ``k x n`` bf16 weight does
``2 m k n`` FLOPs and moves the weight once, its activation in once and
its result out once (``2 (k n + m k + m n)`` bytes; a bias adds ``2 n``).
The least time of a call on a chip is the larger of FLOPs over the peak
rate and bytes over the peak bandwidth, and the bound is named by which.
Peaks come from ``bench/peaks.json``, keyed by the device kind JAX
reports; a kind that is not in the table is an error.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
BYTES = 2                                   # bf16


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add the chip's published peaks")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Matmul:
    name: str
    m: int
    k: int
    n: int
    bias: bool = False

    @property
    def flops(self) -> float:
        return 2.0 * self.m * self.k * self.n

    @property
    def bytes(self) -> float:
        b = self.k * self.n + self.m * self.k + self.m * self.n
        return BYTES * (b + (self.n if self.bias else 0))


def layer_linears(model: dict) -> list:
    """(name, k, n, bias) of one layer's projections."""
    d, h, kh, hd, ff = (model["d_model"], model["num_heads"],
                        model["num_kv_heads"], model["head_dim"],
                        model["d_ff"])
    b = bool(model["qkv_bias"])
    return [("wq", d, h * hd, b), ("wk", d, kh * hd, b),
            ("wv", d, kh * hd, b), ("wo", h * hd, d, False),
            ("w_gate", d, ff, False), ("w_up", d, ff, False),
            ("w_down", ff, d, False)]


def step_matmuls(model: dict, m: int, kernel_ns=None) -> list:
    """Every matmul of one program execution over ``m`` rows: each
    layer's projections and the output head.  ``kernel_ns`` keeps only
    those whose output width the program runs as a kernel."""
    out = []
    for layer in range(model["num_layers"]):
        for name, k, n, b in layer_linears(model):
            out.append(Matmul(f"{name}.{layer}", m, k, n, b))
    out.append(Matmul("head", m, model["d_model"], model["vocab_size"]))
    if kernel_ns is not None:
        out = [c for c in out if c.n in kernel_ns]
    return out


def least_time(calls, peak: dict) -> tuple:
    """(seconds, bound) over calls that run one after another: each
    call's larger of compute and memory time, summed; the bound names
    which of the two makes up most of the sum."""
    t_c = t_m = total = 0.0
    for c in calls:
        tc = c.flops / peak["bf16_flops_per_s"]
        tm = c.bytes / peak["hbm_bytes_per_s"]
        total += max(tc, tm)
        if tc >= tm:
            t_c += tc
        else:
            t_m += tm
    return total, ("compute" if t_c > t_m else "memory")


def matmul_params(model: dict) -> int:
    """Weights multiplied per token: every projection and the head."""
    per_layer = sum(k * n for _, k, n, _ in layer_linears(model))
    return model["num_layers"] * per_layer + model["d_model"] * model[
        "vocab_size"]


def token_flops(model: dict, context: int) -> float:
    """Model FLOPs of one token that attends over ``context`` positions:
    its matmuls, and QK^T and PV against the context in every layer."""
    attn = 4.0 * model["num_layers"] * model["num_heads"] * model[
        "head_dim"] * context
    return 2.0 * matmul_params(model) + attn


def prompt_flops(model: dict, p: int) -> float:
    """Model FLOPs of prefilling a ``p``-token prompt (causal: position
    ``i`` attends over ``i + 1`` positions)."""
    attn = 4.0 * model["num_layers"] * model["num_heads"] * model[
        "head_dim"] * (p * (p + 1) / 2)
    return 2.0 * matmul_params(model) * p + attn
