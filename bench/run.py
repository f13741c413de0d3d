#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  Set-up: plans for exactly the engine's
buckets (an install sweep in the first run of a cell in a checkout, then
read from ``.cache/bench/cells/<cell>/``), seeded bf16 weights made on the
device in one jitted call, the ``Engine`` (weights packed once), and one
warm-up pool that serves every prompt-length bucket the mix uses, so that
every program is compiled or loaded before the window opens.  The window
then drives ``AsyncEngine.run`` on the real clock for ``--seconds``
(``bench/serve_loop.py``).  With ``--trace 1`` a profiler trace of part
of the window feeds the per-layer metrics (``bench/metrics/<name>.py``).
After the window the program's state is freed and the plain reference
decides ``correct`` (``bench/check.py``).

Exits 2 and prints no result without a TPU (or with fewer chips than the
cell asks for) or outside a checkout of the repository; exits 1 on a
degradation-ladder demotion, a plan-registry miss after install, too few
planned kernels in a served program, or a compilation inside the window.
The last line of standard output is the result, one JSON object.
"""

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import traffic  # noqa: E402

STATE = ROOT / ".cache" / "bench"
METRICS_DIR = ROOT / "bench" / "metrics"
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
KERNEL_NAME = re.compile(r"^\s*(?:ROOT\s+)?%tsmm_(\w+?)(?:\.\d+)* = ")
DRAIN_S = 60.0             # an open loop's wait for its last requests
CLOSED_REQUESTS = 4096     # a closed loop's queue, more than a window takes
TRACE_AT = 0.3             # the traced part of the window: its start...
TRACE_S = 10.0             # ...and length (at most the window's rest)


class Failure(Exception):
    """A guard of the served path failed: no result is printed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict               # bench/configs/<config>.json
    mix: dict                  # bench/traffic/<mix>.json
    end_to_end: list           # BENCHMARK.json metric entries of this cell
    per_layer: list


def _for_cell(entries, cell: str) -> list:
    return [m for m in entries if "workloads" not in m
            or cell in m["workloads"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                mix=traffic.load_mix(w["traffic"]),
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name))


def isolate(cell: str) -> None:
    """Every cache at a fixed path inside the checkout; nothing from the
    environment that would steer the planned kernels."""
    for var in ("REPRO_TSMM_VARIANT", "REPRO_TSMM_SCHEDULE"):
        if os.environ.get(var):
            raise SystemExit(f"bench: {var} overrides the planned kernels")
    if os.environ.get("REPRO_TSMM_IMPL", "") not in ("", "auto", "pallas"):
        raise SystemExit("bench: REPRO_TSMM_IMPL would leave the Pallas path")
    cell_dir = STATE / "cells" / cell
    cell_dir.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(STATE / "jax")
    os.environ["REPRO_PROGRAM_CACHE"] = str(STATE / "programs")
    os.environ["REPRO_PLAN_CACHE"] = str(cell_dir / "plans.json")
    os.environ["REPRO_MEASURE_CACHE"] = str(cell_dir / "measurements.json")
    os.environ["REPRO_MISS_LOG"] = str(cell_dir / "misses.json")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for var in ("REPRO_FIND_DB", "REPRO_TUNE_QUEUE"):
        os.environ.pop(var, None)


class Compiles:
    """XLA compilations and persistent-cache reads, from JAX's events."""

    def __init__(self):
        self.count = 0

    def watch(self) -> None:
        from jax import monitoring

        def on_duration(event, duration_secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        monitoring.register_event_duration_secs_listener(on_duration)


def kernel_leaves(hlo: str) -> tuple:
    """(number of tpu_custom_call ops, set of the leaf names of those the
    program named ``tsmm_<leaf>``): an instruction keeps its kernel's
    name, with XLA's ``.<n>`` suffix."""
    leaves = set()
    n = 0
    for line in hlo.splitlines():
        if CUSTOM_CALL in line and " = " in line:
            n += 1
            m = KERNEL_NAME.match(line)
            if m:
                leaves.add(m.group(1))
    return n, leaves


def param_leaves(model) -> tuple:
    """({path: (shape, dtype, stacked)}, logical axes) of the params of
    ``model`` (``repro.models.registry.build_model``), from shapes alone;
    a stacked leaf (under ``layers``) has a leading layer dim."""
    import jax
    captured = {}

    def init_shapes(rng):
        p, a = model.init(rng)
        captured["axes"] = a
        return p

    shapes = jax.eval_shape(init_shapes, jax.random.PRNGKey(0))
    leaves = {}

    def walk(t, path):
        if isinstance(t, dict):
            for k in t:
                walk(t[k], path + (k,))
        else:
            leaves["/".join(path)] = (tuple(t.shape), t.dtype,
                                      path[0] == "layers")

    walk(shapes, ())
    return leaves, captured["axes"]


def seeded_params(leaves: dict, seed: int) -> dict:
    """The nested params of ``leaves`` from ``seed`` (``bench/weights.py``),
    made on the default device in one jitted call."""
    import jax
    import jax.numpy as jnp
    from bench import weights as W
    words = jnp.asarray(W.seed_words(seed))
    flat = jax.jit(lambda w: W.make_tree(w, leaves))(words)
    return nest(jax.block_until_ready(flat))


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        d = out
        *head, last = path.split("/")
        for k in head:
            d = d.setdefault(k, {})
        d[last] = v
    return out


class Bench:
    """Set-up, window, measurement and check of one cell for one seed."""

    def __init__(self, cell: Cell, seed: int, *, trace: bool = False):
        self.cell = cell
        self.seed = seed
        self.trace = trace
        self.engine_cfg = cell.config["engine"]
        self.model_spec = cell.config["model"]
        self.compiles = Compiles()
        self.events = []           # (name, seconds) of set-up phases

    @contextlib.contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        yield
        self.events.append((name, time.perf_counter() - t))

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        from repro.configs.base import ModelConfig
        from repro.core import install, registry
        from repro.core.plan import buckets_for, length_buckets_for
        from repro.models.registry import build_model
        from repro.serve.engine import Engine
        from repro.serve.programs import enable_compilation_cache

        enable_compilation_cache()
        self.compiles.watch()
        e = self.engine_cfg
        self.cfg = ModelConfig(**self.model_spec)
        self.model = build_model(self.cfg)
        buckets = buckets_for(e["slots"])
        lengths = length_buckets_for(e["max_prompt"])
        with self.phase("plans"):
            plans = Path(os.environ["REPRO_PLAN_CACHE"])
            if not plans.exists():
                install.install_arch(self.cfg, buckets, lengths)
                registry.flush()
            self.misses0 = registry.stats()["misses"]

        with self.phase("weights"):
            leaves, axes = param_leaves(self.model)
            params = seeded_params(leaves, self.seed)

        with self.phase("engine"):
            self.eng = Engine(self.model, params, axes,
                              max_len=e["max_len"], max_batch=e["slots"],
                              max_prompt=e["max_prompt"],
                              donate_params=True)
            del params
        self.slots = self.eng.buckets[-1]
        if self.slots != e["slots"]:
            raise Failure(f"slots {e['slots']} snapped to {self.slots}")

        with self.phase("warm-up"):
            asyncio.run(self._warm())
        self._programs()
        misses = registry.stats()["misses"] - self.misses0
        if misses:
            raise Failure(f"{misses} plan-registry misses after install")

    async def _warm(self) -> None:
        """Serve one request per prompt-length bucket the mix uses, in a
        pool that is then closed: every program of the window compiled or
        loaded, and the pool's reopen path run once."""
        from bench.serve_loop import Server, make_record
        import numpy as np
        used = traffic.length_buckets_used(self.cell.mix,
                                           self.eng.grid.length)
        server = Server(self.eng, self.cell.mix, self.slots)
        await server.start()
        recs = []
        for i, lb in enumerate(used):
            req = traffic.Req(index=-1 - i, due=0.0,
                              prompt=np.full(lb, 7, np.int32), out_len=3)
            rec = make_record(self.eng, req, time.perf_counter())
            recs.append(rec)
            server.offer(rec)
        for rec in recs:
            await rec.finished.wait()
        await server.stop()
        if not all(r.ok for r in recs):
            raise Failure("a warm-up request did not complete")

    def _programs(self) -> None:
        """The module name of the stored programs, the leaf names of each
        program's kernels, and the planned-kernel guard."""
        import jax
        self.kernels, self.module_names = {}, set()
        # off the chip the kernels run as XLA or in interpret mode
        need = len(self.eng.pack_report) if jax.default_backend() == "tpu" \
            else 0
        for prog in self.eng.programs.handles():
            tokens = int(re.search(r"_t(\d+)_", prog.key).group(1))
            name = ("decode_step" if prog.kind == "decode"
                    else f"{prog.kind}.{tokens}")
            hlo = prog.executable.as_text()
            self.module_names.add(hlo.split(",", 1)[0].split()[-1])
            n, leaves = kernel_leaves(hlo)
            if n < need:
                raise Failure(f"{name}: {n} tpu_custom_call < {need} "
                              f"packed weights")
            self.kernels[name] = leaves

    # -- window ------------------------------------------------------------

    def serve(self, seconds: float) -> None:
        """Run the window (and an open loop's drain) on the cell's mix."""
        gc.collect()
        gc.disable()
        try:
            asyncio.run(self._serve(seconds))
        finally:
            gc.enable()

    async def _serve(self, seconds: float) -> None:
        from bench.serve_loop import Server, run_closed, run_open
        mix = self.cell.mix
        vocab = self.model_spec["vocab_size"]
        if mix["loop"] == "open":
            count = traffic.open_count(mix, seconds)
            reqs = [r for r in traffic.requests(mix, count, self.seed, vocab)
                    if r.due < seconds]
        else:
            reqs = iter(traffic.requests(mix, CLOSED_REQUESTS, self.seed,
                                         vocab))
        server = Server(self.eng, mix, self.slots, spans=self.trace)
        await server.start()
        tracer = None
        self.t0 = time.perf_counter() + 0.05
        self.setup_s = self.t0 - T_PROC
        self.compiles_at_t0 = self.compiles.count
        self.counters0 = server.counters()
        if self.trace:
            tracer = asyncio.ensure_future(self._trace(seconds))
        if mix["loop"] == "open":
            self.records = await run_open(server, reqs, self.t0, DRAIN_S)
            self.t1 = self.t0 + seconds
            self.counters1 = server.counters()
            await server.stop(abort=not all(r.done for r in self.records))
        else:
            self.records = await run_closed(server, reqs, mix["clients"],
                                            self.t0, seconds)
            self.t1 = self.t0 + seconds
            self.counters1 = server.counters()
            await server.stop(abort=True)
        if tracer is not None:
            await tracer
        self.t_end = time.perf_counter()
        self.window_compiles = self.compiles.count - self.compiles_at_t0
        self.pools = server.pools

    async def _trace(self, seconds: float) -> None:
        import jax
        from bench.serve_loop import sleep_until
        start = self.t0 + TRACE_AT * seconds
        length = min(TRACE_S, (1 - TRACE_AT) * seconds)
        out = STATE / "trace" / self.cell.name
        shutil.rmtree(out, ignore_errors=True)
        # host spans and dispatch events only: the Python function tracer
        # would slow the asyncio loop that serves the window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        # the profiler starts and stops on a worker thread, so that the
        # loop serving the window is not held while it does
        loop = asyncio.get_running_loop()
        await sleep_until(start)
        t = time.perf_counter()
        await loop.run_in_executor(None, lambda: jax.profiler.start_trace(
            str(out), profiler_options=opts))
        self.profiler_s = [time.perf_counter() - t]
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        self.tw = (time.perf_counter(), None)
        await sleep_until(start + length)
        self.tw = (self.tw[0], time.perf_counter())
        span.__exit__(None, None, None)
        t = time.perf_counter()
        await loop.run_in_executor(None, jax.profiler.stop_trace)
        self.profiler_s.append(time.perf_counter() - t)
        self.trace_dir = out

    # -- guards and measurement ---------------------------------------------

    def guards(self) -> None:
        from repro.core import registry
        if self.window_compiles:
            raise Failure(f"{self.window_compiles} compilations inside the "
                          f"window")
        misses = registry.stats()["misses"] - self.misses0
        if misses:
            raise Failure(f"{misses} plan-registry misses while serving")
        hr = self.eng.health_report()
        if not hr["healthy"]:
            raise Failure(f"{hr['degradations']['total']} degradation-"
                          f"ladder demotions while serving")

    def end_to_end(self) -> dict:
        from bench.serve_loop import percentile
        mix, recs = self.cell.mix, self.records
        t0, t1 = self.t0, self.t1
        if mix["loop"] == "open":
            self.attempted = len(recs)
            self.failed = sum(not r.ok for r in recs)
            ok = [r for r in recs if r.ok]
            ttft = [(r.times[0] - r.due) * 1e3 for r in ok]
            itl = [(b - a) * 1e3 for r in ok
                   for a, b in zip(r.times, r.times[1:])]
            tokens = sum(len(r.times) for r in ok)
        else:
            self.attempted = len(recs)
            self.failed = sum(1 for r in recs if r.done is not None
                              and r.done <= t1 and not r.ok)
            ttft = [(r.times[0] - r.due) * 1e3 for r in recs
                    if r.times and r.times[0] <= t1]
            itl = [(b - a) * 1e3 for r in recs
                   for a, b in zip(r.times, r.times[1:]) if t0 <= a and b <= t1]
            tokens = sum(1 for r in recs for t in r.times if t0 <= t < t1)
        values = {"itl_p95_ms": percentile(itl, 95),
                  "tokens_per_s": tokens / (t1 - t0),
                  "setup_s": self.setup_s}
        if ttft:
            print("ttft ms: p50 {} p90 {} max {} over {} requests".format(
                percentile(ttft, 50), percentile(ttft, 90), max(ttft),
                len(ttft)), file=sys.stderr)
        if itl:
            # a gap half again the median's carries an admission's prefill
            mid = percentile(itl, 50)
            print("itl ms: p50 {} p90 {} p95 {} p99 {} over {} gaps, {}% "
                  "of them over 1.5 x p50".format(
                      mid, percentile(itl, 90), percentile(itl, 95),
                      percentile(itl, 99), len(itl),
                      100.0 * sum(g > 1.5 * mid for g in itl) / len(itl)),
                  file=sys.stderr)
        out = {}
        for m in self.cell.end_to_end:
            v = values.get(m["name"])
            if v is None:
                raise Failure(f"end-to-end metric {m['name']} has no value")
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out

    def device(self) -> dict:
        import jax
        devs = jax.devices()
        used = devs[:self.cell.chips]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs), "memory_peak_bytes": int(peak)}

    def per_layer(self, device: dict) -> tuple:
        from bench import counts
        from bench import trace as T
        pb = glob.glob(str(self.trace_dir / "**" / "*.xplane.pb"),
                       recursive=True)
        if not pb:
            raise Failure("the profiler wrote no trace")
        red = T.reduce(pb[0], tuple(self.module_names))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        for name, row in sorted(red.by_kind().items()):
            print(f"trace: {name} x{row['n']}: {row['ns'] * 1e-6:.3f} ms "
                  f"device, {row['custom_ns'] * 1e-6:.3f} ms in "
                  f"tpu_custom_call; kernel leaves "
                  f"{sorted(self.kernels.get(name, ()))}", file=sys.stderr)
        window_ns = red.window[1] - red.window[0]
        device["busy_s"] = red.busy_ns * 1e-9
        device["window_s"] = window_ns * 1e-9
        ctx = MetricContext(
            model=self.model_spec, peak=counts.peaks(device["kind"]),
            reduced=red, records=self.records, trace_window=self.tw,
            kernels=self.kernels, slots=self.slots, mix=self.cell.mix,
            counters=(self.counters0, self.counters1))
        out = {}
        for m in self.cell.per_layer:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        ops = sorted(red.op_self_ns.items(), key=lambda kv: -kv[1])[:10]
        idle = red.idle_by_host(self._pool_state(red))
        breakdown = {"device_ops": [[k, v * 1e-9] for k, v in ops],
                     "idle_gaps": [[k, v] for k, v in idle[:10]]}
        print("profiler start {:.3f} s, stop {:.3f} s; idle gaps: {}".format(
            *self.profiler_s, idle), file=sys.stderr)
        return out, breakdown

    def _pool_state(self, red):
        """What the clients' requests were doing at a time on the trace's
        device clock: the traced window's span opened at ``self.tw[0]``
        on the host clock."""
        spans = [s for n, s, _ in red.host_spans if n == "window"]
        if not spans:
            return None
        t_host = self.tw[0] - spans[0] * 1e-9

        def state(t_ns: float) -> str:
            t = t_host + t_ns * 1e-9
            running = sum(1 for r in self.records if r.times
                          and r.times[0] <= t and (r.done is None
                                                   or r.done > t))
            waiting = sum(1 for r in self.records if r.due <= t
                          and (not r.times or r.times[0] > t))
            if running:
                return "requests running"
            return "requests waiting" if waiting else "no request due"

        return state

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        import jax
        for leaf in jax.tree.leaves(self.eng.params):
            if hasattr(leaf, "delete"):
                leaf.delete()
        self.eng = None
        gc.collect()

    def check(self) -> dict:
        import jax
        from bench import check
        mix = self.cell.mix
        k = int(mix["sample"])
        length = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
        sample = check.draw_sample(self.records, k, self.seed)
        seqs = check.sequences(sample)
        ref = check.reference_class(self.cell.config["reference"])(
            self.model_spec, self.seed)
        with jax.default_matmul_precision("highest"):
            gaps = check.served_gaps(ref, seqs, k, length)
        limit = self.cell.config["check"]["served_logit_gap"]
        widest = float(gaps.max()) if len(gaps) else float("inf")
        self.sample_info = {"requests": len(sample),
                            "served_tokens": int(len(gaps))}
        return {"served_logit_gap": {"value": widest, "limit": limit}}


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader gets."""
    model: dict
    peak: dict
    reduced: object            # bench.trace.Reduced
    records: list              # bench.serve_loop.Record
    trace_window: tuple        # host perf_counter (start, end)
    kernels: dict              # program name -> kernel leaf names
    slots: int
    mix: dict
    counters: tuple            # scheduler counters at window open, close


def load_metric(name: str, directory: Path = METRICS_DIR):
    """The reader of metric ``name``: ``<name>.py``, or for a metric split
    by the end-to-end metric it moves (``<metric>.<suffix>``, with no file
    of its own) the reader of ``<metric>``."""
    path = directory / f"{name}.py"
    if not path.is_file() and "." in name:
        path = directory / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def execute(cell: Cell, seed: int, seconds: float, trace: bool) -> dict:
    b = Bench(cell, seed, trace=trace)
    b.setup()
    b.serve(seconds)
    b.guards()
    metrics = b.end_to_end()
    device = b.device()
    result = {"correct": False, "attempted": b.attempted,
              "failed": b.failed, "metrics": metrics, "device": device}
    if trace:
        metrics, breakdown = b.per_layer(device)
        result["metrics"] = metrics
        result["breakdown"] = breakdown
    b.free()
    checks = b.check()
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    result["checks"] = checks
    print(f"set-up {b.setup_s:.3f} s: " + ", ".join(
        f"{n} {s:.3f} s" for n, s in b.events) + f"; pools {b.pools}",
        file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}; "
              f"{b.sample_info['requests']} requests, "
              f"{b.sample_info['served_tokens']} served tokens)",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no src/repro in {ROOT}: run from a checkout of the "
              f"repository", file=sys.stderr)
        return 2
    try:
        cell = load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    isolate(cell.name)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        result = execute(cell, args.seed, args.seconds, bool(args.trace))
    except Failure as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
