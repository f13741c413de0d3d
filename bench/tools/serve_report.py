#!/usr/bin/env python3
"""One traced run of a benchmark cell, with the program's own spans and
scopes read out of the trace.

    python3 bench/tools/serve_report.py --workload <cell> --seed <n> \
        --seconds <s> [--keep DIR]

Runs the cell as ``bench/run.py --trace 1`` does (the same ``Bench``:
set-up, window, guards, per-layer metrics, check), and before the
harness reduces and deletes the trace reads it with ``bench/spans.py``:

- ``host_gap_ms``, ``decode_scan_ms`` and ``decode_attention_ms``
  through their readers in ``bench/metrics/``;
- each program's device time split by named scope (``kernels`` for every
  Pallas kernel), and how far the split falls short of the programs'
  time;
- the clock check: the share of ``decode_step`` executions that start
  on the device inside their ``serve.step`` span after its
  ``serve.dispatch`` began;
- idle gaps labelled by the harness span, the innermost ``serve.*`` span
  and the clients' state;
- kernel time per packed leaf against the leaf's least time
  (``bench/counts.py``).

The report goes to standard error; the last line of standard output is
the harness's result with the span metrics added under ``"spans"``.
``--keep`` copies the trace and the programs' scope maps there.  Needs
the chip the cell names.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import shutil
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import counts  # noqa: E402
from bench import run as R  # noqa: E402
from bench import spans  # noqa: E402

METRICS = ("host_gap_ms", "decode_scan_ms", "decode_attention_ms")


def log(*a) -> None:
    print(*a, file=sys.stderr)


def split_table(st, prefix: str) -> None:
    got = spans.split(st, prefix)
    if not got or not got[1]:
        log(f"split {prefix}: no scoped executions")
        return
    by, n, total = got
    log(f"split {prefix} ({n} executions, {total / n * 1e-6:.3f} ms each):")
    for label, ns in sorted(by.items(), key=lambda kv: -kv[1]):
        log(f"  {label:12s} {ns / n * 1e-6:9.3f} ms  {100 * ns / total:6.2f}%")
    log(f"  {'(sum)':12s} {sum(by.values()) / n * 1e-6:9.3f} ms; closes "
        f"within {100 * abs(total - sum(by.values())) / total:.3f}%")


def leaf_least_s(model: dict, m: int, peak: dict) -> dict:
    """Least time (``bench/counts.py``) of one program execution's
    matmuls of each leaf over ``m`` rows: every layer's, and the head;
    routed experts left out, since no kernel runs them."""
    out = collections.Counter()
    for c in counts.step_matmuls(model, m):
        if not c.routed:
            out[c.leaf] += counts.least_time([c], peak)[0]
    return dict(out)


def leaf_table(st, prefix: str, model: dict, m: int, peak: dict) -> None:
    n = len(spans.executions(st, prefix))
    if not n:
        return
    least = leaf_least_s(model, m, peak)
    log(f"kernels per leaf, {prefix} (m = {m}, {n} executions; ms each):")
    for leaf, ns in sorted(spans.kernel_leaves(st, prefix).items(),
                           key=lambda kv: -kv[1]):
        t = ns / n * 1e-9
        lt = least.get(leaf)
        share = f"{100 * lt / t:6.2f}% of roofline" if lt else ""
        log(f"  {leaf:8s} {t * 1e3:9.4f} ms  least "
            f"{lt * 1e3 if lt else float('nan'):9.4f} ms  {share}")


def report(b, st, device: dict) -> dict:
    ctx = types.SimpleNamespace(spans=st)
    out = {}
    for name in METRICS:
        v = R.load_metric(name).read(ctx)
        log(f"span metric {name}: {v!r}")
        if v is not None:
            out[name] = v
    split_table(st, "decode_step")
    split_table(st, "prefill_row")
    ok, n = spans.clock_check(st)
    log(f"clock check: {ok} of {n} decode_step executions start after "
        f"their serve.dispatch ({100 * ok / max(n, 1):.2f}%)")
    out["clock_check_share"] = 100 * ok / n if n else None
    lags = sorted(spans.dispatch_lags(st)[0])
    if lags:
        log("decode_step start after its serve.dispatch began (ms): "
            "min {:.4f} median {:.4f} p90 {:.4f} max {:.4f}".format(
                *(lags[int(q * (len(lags) - 1))] * 1e-6
                  for q in (0, 0.5, 0.9, 1))))
    state = b._pool_state(types.SimpleNamespace(
        host_spans=[("window", *st.window)]))
    idle = spans.idle_labels(st, state)
    total = sum(v for _, v, _ in idle)
    log(f"idle gaps ({total:.6f} s):")
    for label, v, k in idle[:20]:
        log(f"  {v:.6f} s  {k:6d} gaps  {label}")
    log("idle gaps by program:")
    for where, v, k in spans.idle_by_program(st)[:12]:
        log(f"  {v:.6f} s  {k:6d} gaps  {where}")
    log("serve spans (count, mean ms):")
    for name, (k, mean) in sorted(spans.span_means(st).items()):
        log(f"  {name:16s} {k:6d}  {mean * 1e-6:9.4f}")
    peak = counts.peaks(device["kind"])
    leaf_table(st, "decode_step", b.model_spec, b.slots, peak)
    lbs = sorted({int(e.program.rsplit("_t", 1)[1])
                  for e in spans.executions(st, "prefill_row")})
    for lb in lbs:
        leaf_table(st, f"prefill_row_b{b.slots}_t{lb}", b.model_spec, lb,
                   peak)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default="")
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    R.isolate(cell.name)
    import jax
    if jax.devices()[0].platform != "tpu":
        log(f"serve_report: {cell.name} needs a TPU")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    b = R.Bench(cell, args.seed, trace=True)
    try:
        b.setup()
        b.serve(args.seconds)
        b.guards()
        result = {"metrics": b.end_to_end()}
        device = b.device()
        pb = glob.glob(str(b.trace_dir / "**" / "*.xplane.pb"),
                       recursive=True)[0]
        scopes = dict(spans.scope_map(p.executable.as_text())
                      for p in b.eng.programs.handles())
        st = spans.read(pb, scopes)
        if args.keep:
            keep = Path(args.keep)
            keep.mkdir(parents=True, exist_ok=True)
            shutil.copy(pb, keep / f"{cell.name}.xplane.pb")
            (keep / f"{cell.name}.scopes.json").write_text(json.dumps(scopes))
        result["spans"] = report(b, st, device)
        per_layer, breakdown = b.per_layer(device)
        result["metrics"].update(per_layer)
        result["breakdown"] = breakdown
        result["device"] = device
        b.free()
        checks = b.check()
    except R.Failure as e:
        log(f"serve_report: {e}")
        return 1
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    result["checks"] = checks
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
