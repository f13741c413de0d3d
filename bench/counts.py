"""Operations and HBM bytes of a decoder LM's work, from shapes alone.

Algorithmic counts, the same whatever plan, block shape or kernel runs a
matmul: a call of ``m`` rows against a ``k x n`` bf16 weight does
``2 m k n`` FLOPs and moves the weight once, its activation in once and
its result out once (``2 (k n + m k + m n)`` bytes; a bias adds ``2 n``).
The least time of a call on a chip is the larger of FLOPs over the peak
rate and bytes over the peak bandwidth, and the bound is named by which.
Peaks come from ``bench/peaks.json``, keyed by the device kind JAX
reports; a kind that is not in the table is an error.

Matmuls are named by the program's leaf names, per family of the model
block (the keys of ``repro.configs.base.ModelConfig``, with its defaults):

- attention: ``wq``/``wk``/``wv``/``wo`` (GQA, with the Q/K/V bias of
  ``qkv_bias``), or with ``use_mla`` latent attention: ``wq`` (or
  ``wq_a``/``wq_b`` when ``q_lora_rank`` > 0), ``wkv_a`` (d to kvr + dr),
  ``wkv_b`` (kvr to h (dn + dv)) and ``wo`` (h dv to d).  Attention over
  the context does ``2 h (dn + dr) + 2 h dv`` FLOPs per pair of
  positions: the expanded form of the DeepSeek-V2 paper, the model's work
  whether the program computes it absorbed or not;
- MLP: ``w_gate``/``w_up``/``w_down`` of width ``d_ff``; with family
  ``moe`` only in the ``first_k_dense`` leading layers.  Every other
  layer has the ``router`` (d to ``num_experts_total``), the shared
  experts ``ws_gate``/``ws_up``/``ws_down`` (width ``num_shared_experts
  d_ff_expert``) and the routed experts ``w_gate``/``w_up``/``w_down``
  (width ``d_ff_expert``).  ``num_experts`` counts the experts held on
  the chip and ``num_experts_total`` the router's published width (the
  same when absent): a token's ``experts_per_token`` experts fall on the
  held ones ``num_experts / num_experts_total`` of the time;
- the output head, d to ``vocab_size``.
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
    """One matmul of ``m`` rows against ``weights`` ``k x n`` weights.
    A ``routed`` call is a routed expert leaf: ``m`` is the rows that the
    held experts receive on average, and ``weights`` the held experts
    that some row reaches, on average, under uniform routing."""
    name: str
    m: float
    k: int
    n: int
    bias: bool = False
    routed: bool = False
    weights: float = 1

    @property
    def leaf(self) -> str:
        return self.name.split(".", 1)[0]

    @property
    def flops(self) -> float:
        return 2.0 * self.m * self.k * self.n

    @property
    def bytes(self) -> float:
        b = self.weights * self.k * self.n + self.m * self.k + self.m * self.n
        return BYTES * (b + (self.n if self.bias else 0))


def _dims(model: dict) -> dict:
    """The model block's sizes, with ``ModelConfig``'s defaults."""
    family = model.get("family", "dense")
    if family not in ("dense", "moe"):
        raise ValueError(f"no counts for family {family!r}")
    hd = model.get("head_dim") or model["d_model"] // model["num_heads"]
    experts = model.get("num_experts", 0)
    return dict(
        d=model["d_model"], h=model["num_heads"], hd=hd,
        kh=model["num_kv_heads"], ff=model["d_ff"],
        mla=bool(model.get("use_mla", False)),
        qr=model.get("q_lora_rank", 0), kvr=model.get("kv_lora_rank", 0),
        dr=model.get("rope_head_dim", 64),
        dv=model.get("v_head_dim") or hd,
        moe=family == "moe", first_dense=model.get("first_k_dense", 0),
        experts=experts,
        experts_total=model.get("num_experts_total") or experts,
        topk=model.get("experts_per_token", 0),
        ffe=model.get("d_ff_expert") or model["d_ff"],
        shared=model.get("num_shared_experts", 0))


def layer_linears(model: dict, layer: int) -> list:
    """(leaf, k, n, bias, held experts) of layer ``layer``'s matmuls;
    held experts is 0 for a leaf that is not a routed expert."""
    s = _dims(model)
    d, h, hd = s["d"], s["h"], s["hd"]
    if s["mla"]:
        dn, dr, dv, kvr = hd, s["dr"], s["dv"], s["kvr"]
        q = ([("wq_a", d, s["qr"]), ("wq_b", s["qr"], h * (dn + dr))]
             if s["qr"] else [("wq", d, h * (dn + dr))])
        out = [(n, k, w, False, 0) for n, k, w in q] + [
            ("wkv_a", d, kvr + dr, False, 0),
            ("wkv_b", kvr, h * (dn + dv), False, 0),
            ("wo", h * dv, d, False, 0)]
    else:
        b = bool(model["qkv_bias"])
        kv = s["kh"] * hd
        out = [("wq", d, h * hd, b, 0), ("wk", d, kv, b, 0),
               ("wv", d, kv, b, 0), ("wo", h * hd, d, False, 0)]
    if not s["moe"] or layer < s["first_dense"]:
        ff = s["ff"]
        return out + [("w_gate", d, ff, False, 0), ("w_up", d, ff, False, 0),
                      ("w_down", ff, d, False, 0)]
    out.append(("router", d, s["experts_total"], False, 0))
    sff = s["shared"] * s["ffe"]
    if sff:
        out += [("ws_gate", d, sff, False, 0), ("ws_up", d, sff, False, 0),
                ("ws_down", sff, d, False, 0)]
    e, ffe = s["experts"], s["ffe"]
    return out + [("w_gate", d, ffe, False, e), ("w_up", d, ffe, False, e),
                  ("w_down", ffe, d, False, e)]


def _routed_share(model: dict) -> float:
    """Routed rows per token that land on one held expert."""
    s = _dims(model)
    return s["topk"] / s["experts_total"] if s["experts_total"] else 0.0


def step_matmuls(model: dict, m: int, kernel_leaves=None) -> list:
    """Every matmul of one program execution over ``m`` rows: each
    layer's and the output head.  ``kernel_leaves`` keeps only those
    whose leaf the program runs as a kernel (``tsmm_<leaf>``); a routed
    expert never passes, since no kernel runs one."""
    share = _routed_share(model)
    out = []
    for layer in range(model["num_layers"]):
        for name, k, n, b, e in layer_linears(model, layer):
            if e:
                out.append(Matmul(f"{name}.{layer}", m * e * share, k, n,
                                  routed=True,
                                  weights=e * (1 - (1 - share) ** m)))
            else:
                out.append(Matmul(f"{name}.{layer}", m, k, n, b))
    out.append(Matmul("head", m, model["d_model"], model["vocab_size"]))
    if kernel_leaves is not None:
        out = [c for c in out if not c.routed and c.leaf in kernel_leaves]
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


def _params(model: dict, per_expert) -> float:
    """Matmul weights of every layer and the head, a routed leaf's
    counted ``per_expert`` times for each held expert."""
    head = model["d_model"] * model["vocab_size"]
    return sum(k * n * (e * per_expert if e else 1)
               for layer in range(model["num_layers"])
               for _, k, n, _, e in layer_linears(model, layer)) + head


def matmul_params(model: dict) -> float:
    """Weights multiplied per token: every layer's matmuls, of a routed
    leaf the share of the held experts a token uses on average, and the
    head."""
    return _params(model, _routed_share(model))


def weight_params(model: dict) -> int:
    """Matmul weights held on the chip: every layer's (each held expert
    once) and the head."""
    return _params(model, 1)


def _pair_flops(model: dict) -> int:
    """Attention FLOPs of one layer for one pair of positions: QK^T and
    PV, in the expanded form for latent attention."""
    s = _dims(model)
    if s["mla"]:
        return 2 * s["h"] * (s["hd"] + s["dr"]) + 2 * s["h"] * s["dv"]
    return 4 * s["h"] * s["hd"]


def token_flops(model: dict, context: int) -> float:
    """Model FLOPs of one token that attends over ``context`` positions:
    its matmuls, and QK^T and PV against the context in every layer."""
    attn = float(model["num_layers"] * _pair_flops(model)) * context
    return 2.0 * matmul_params(model) + attn


def prompt_flops(model: dict, p: int) -> float:
    """Model FLOPs of prefilling a ``p``-token prompt (causal: position
    ``i`` attends over ``i + 1`` positions)."""
    attn = float(model["num_layers"] * _pair_flops(model)) * (p * (p + 1)
                                                              / 2)
    return 2.0 * matmul_params(model) * p + attn
