"""Drive a whole benchmark run at a tiny size, on whatever backend JAX
has, without the harness's look for a chip; optionally with a fault
planted in the served path.  Prints the result's JSON as its last line.

    python bench/tests/drive_tiny.py --state DIR [--fault NAME] [--seed N]

Faults (each must make ``correct`` come out false):

- ``token``: a token altered where it is produced (the scheduler's decode
  step hands out a different token than the model chose, for one row);
- ``stale_cache``: the decode step returns its cache unchanged, so every
  token after the first attends over a cache without its predecessors.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

MODEL = dict(name="tiny", family="dense", num_layers=2, d_model=512,
             num_heads=4, num_kv_heads=2, head_dim=128, d_ff=1024,
             vocab_size=2048, qkv_bias=True, rope_theta=10000.0,
             norm_eps=1e-6, tie_embeddings=False, dtype="bfloat16")
MIX = dict(loop="open", rate_rps=4.0,
           prompt_tokens=dict(median=24, sigma=0.6, min=8, max=64),
           output_tokens=dict(median=8, sigma=0.5, min=4, max=16),
           block=16, queue_limit=64, prefill_budget=32, sample=4)
ENGINE = dict(slots=4, max_len=160, max_prompt=64)


def plant(fault: str) -> None:
    from repro.models import registry
    from repro.serve import scheduler

    if fault == "token":
        step = scheduler.ContinuousScheduler.step

        def altered(self):
            emitted, finished = step(self)
            if emitted:
                st, tok, t = emitted[0]
                bad = (tok + 1) % MODEL["vocab_size"]
                st["emitted"][-1] = bad
                self.feed[st["row"]] = bad
                emitted[0] = (st, bad, t)
            return emitted, finished

        scheduler.ContinuousScheduler.step = altered
    elif fault == "stale_cache":
        build = registry.build_model

        def stale(cfg):
            m = build(cfg)
            return dataclasses.replace(
                m, decode_step=lambda p, c, t: (m.decode_step(p, c, t)[0],
                                                c))

        registry.build_model = stale
    elif fault:
        raise SystemExit(f"unknown fault {fault!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--state", required=True)
    ap.add_argument("--fault", default="")
    ap.add_argument("--seed", type=int, default=3_000_000_019)
    ap.add_argument("--limit", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    state = Path(args.state)
    os.environ["REPRO_PLAN_CACHE"] = str(state / "plans.json")
    os.environ["REPRO_MEASURE_CACHE"] = str(state / "measurements.json")
    os.environ["REPRO_MISS_LOG"] = str(state / "misses.json")
    os.environ["REPRO_PROGRAM_CACHE"] = str(state / "programs")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as R

    plant(args.fault)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = dict(model=MODEL, engine=ENGINE, reference="dense_lm",
                  check={"served_logit_gap": args.limit})
    cell = R.Cell(name="tiny.chat", chips=1, config=config, mix=MIX,
                  end_to_end=R._for_cell(spec["end_to_end"],
                                         "qwen1.5-4b.chat"),
                  per_layer=[])
    print(json.dumps(R.execute(cell, args.seed, args.seconds, False)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
