#!/usr/bin/env python3
"""Readings that the correctness limit of a cell is set from.

    python3 bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--seconds 45] [--control N]

For each seed, in one process: the cell's set-up, a window at the cell's
own load, the program's state freed, then the widest gap of the served
tokens against the plain reference (the number a run compares), and for
the first ``--control`` seeds the same for the control: the reference in
float8 weights, read at every position of the same sequences.  One JSON line per seed.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)

    from bench import run as R
    cell = R.load_cell(args.workload)
    R.isolate(cell.name)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import numpy as np
    from bench import check

    mix = cell.mix
    k = int(mix["sample"])
    length = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        b = R.Bench(cell, seed)
        b.setup()
        b.serve(args.seconds)
        b.guards()
        b.end_to_end()
        b.free()
        seqs = check.sequences(check.draw_sample(b.records, k, seed))
        Ref = check.reference_class(cell.config["reference"])
        row = {"seed": seed, "requests": len(seqs), "failed": b.failed}
        with jax.default_matmul_precision("highest"):
            ref = Ref(cell.config["model"], seed)
            gaps = check.served_gaps(ref, seqs, k, length)
            row.update(program_gap=float(gaps.max()),
                       program_flipped=float(np.mean(gaps > 0)),
                       tokens=int(len(gaps)))
            if i < args.control:
                ctl = Ref(cell.config["model"], seed, quant="fp8")
                cg = check.control_gaps(ref, ctl, seqs, k, length)
                row.update(control_gap=float(cg.max()),
                           control_flipped=float(np.mean(cg > 0)))
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        del b
    return 0


if __name__ == "__main__":
    sys.exit(main())
