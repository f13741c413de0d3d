"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns them into requests from a seed.

A seed changes the order of the work, never its amount.  Prompt and
output lengths are the quantiles of the mix's clipped lognormal at
evenly spaced probabilities, and open-loop gaps the quantiles of the
exponential at the mix's rate; each block of ``block`` requests holds
the whole quantile set once, shuffled by the seed.  So every seed serves
the same lengths and the same gaps, and a window that takes any number
of whole blocks has the same work; short blocks also spread the long
requests and the short gaps evenly over the window.  Token ids are drawn
from the seed.

Mix keys:

- ``loop``: ``open`` (``rate_rps`` arrivals per second, due times fixed
  in advance) or ``closed`` (``clients`` callers, each sending its next
  request when the last one has finished);
- ``prompt_tokens`` / ``output_tokens``: ``median``, ``sigma`` (of the
  log), ``min``, ``max``;
- ``block``: requests per quantile set;
- ``queue_limit``, ``prefill_budget`` (null: none): front-end settings;
- ``sample``: requests the correctness check compares.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "traffic"
LOOPS = ("open", "closed")


@dataclasses.dataclass
class Req:
    """One generated request: ``due`` is seconds after the window opens
    (open loop; 0 for a closed loop)."""
    index: int
    due: float
    prompt: np.ndarray
    out_len: int


def load_mix(name: str, directory: Path = MIX_DIR) -> dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"mix {name}: loop must be one of {LOOPS}")
    return mix


def length_set(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths: clipped lognormal quantiles at (i + 0.5) / n."""
    nd = NormalDist()
    z = np.asarray([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def gap_set(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival quantiles at rate ``rate``."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


def _blocked(values: np.ndarray, count: int, rng) -> np.ndarray:
    """``count`` values: whole shuffled copies of ``values`` in turn."""
    reps = -(-count // len(values))
    return np.concatenate([rng.permutation(values)
                           for _ in range(reps)])[:count]


def requests(mix: dict, count: int, seed: int, vocab: int) -> list:
    """The first ``count`` requests of the mix for ``seed``."""
    rng = np.random.default_rng(seed)
    block = int(mix["block"])
    prompts = _blocked(length_set(mix["prompt_tokens"], block), count, rng)
    outs = _blocked(length_set(mix["output_tokens"], block), count, rng)
    if mix["loop"] == "open":
        gaps = _blocked(gap_set(mix["rate_rps"], block), count, rng)
        dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        dues = np.zeros(count)
    out = []
    for i in range(count):
        toks = rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int32)
        out.append(Req(index=i, due=float(dues[i]), prompt=toks,
                       out_len=int(outs[i])))
    return out


def open_count(mix: dict, seconds: float) -> int:
    """Requests to generate for a window of ``seconds`` (open loop):
    whole blocks that reach past the window's end; the window takes
    those due before it closes."""
    block = int(mix["block"])
    return block * max(1, math.ceil(mix["rate_rps"] * seconds / block) + 1)


def length_buckets_used(mix: dict, buckets: tuple) -> tuple:
    """The prompt-length buckets the mix's prompts fall into."""
    lens = length_set(mix["prompt_tokens"], int(mix["block"]))
    used = {min(b for b in buckets if b >= n) for n in lens}
    return tuple(sorted(used))
