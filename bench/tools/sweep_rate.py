#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one set-up, then one window per
offered rate, each on a fresh pool.

    python3 bench/tools/sweep_rate.py --workload <cell> --rates 0.5,0.8 \
        [--seconds 45] [--seed N] [--mix '{"output_tokens": {...}}']

Prints one JSON line per rate: requests due, failed, TTFT and
inter-token percentiles, and the backlog (requests due and not yet
given their first token) every five seconds of the window.  The knee is
the highest rate whose backlog does not grow over the window and whose
requests all complete.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def backlog(records, t0, seconds, step=5.0):
    out = []
    t = t0 + step
    while t <= t0 + seconds + 1e-9:
        out.append(sum(1 for r in records if r.due <= t
                       and (not r.times or r.times[0] > t)))
        t += step
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=3_000_000_101)
    ap.add_argument("--mix", default="{}")
    args = ap.parse_args(argv)

    from bench import run as R
    from bench.serve_loop import percentile
    cell = R.load_cell(args.workload)
    R.isolate(cell.name)
    sys.path.insert(0, str(ROOT / "src"))
    cell = dataclasses.replace(cell, mix={**cell.mix,
                                          **json.loads(args.mix)})
    b = R.Bench(cell, args.seed)
    b.setup()
    for rate in (float(x) for x in args.rates.split(",")):
        b.cell = dataclasses.replace(cell, mix={**cell.mix,
                                                "rate_rps": rate})
        b.serve(args.seconds)
        recs = b.records
        ok = [r for r in recs if r.ok]
        ttft = [(r.times[0] - r.due) * 1e3 for r in ok]
        itl = [(y - x) * 1e3 for r in ok for x, y in zip(r.times, r.times[1:])]
        wait = [(r.stream.admitted_time - r.due) * 1e3 for r in ok]
        print(json.dumps({
            "rate": rate, "due": len(recs), "failed": len(recs) - len(ok),
            "ttft_ms": [percentile(ttft, q) for q in (50, 90, 99)],
            "itl_ms": [percentile(itl, q) for q in (50, 95, 99)],
            "queue_wait_p90_ms": percentile(wait, 90),
            "backlog_every_5s": backlog(recs, b.t0, args.seconds),
            "pools": b.pools, "compiles_in_window": b.window_compiles,
            "tokens": sum(len(r.times) for r in ok),
            "drain_s": b.t_end - b.t1}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
