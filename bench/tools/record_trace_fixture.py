#!/usr/bin/env python3
"""Record the small device trace that ``bench/tests`` reduces.

    python3 bench/tools/record_trace_fixture.py [--out DIR]

Runs on a TPU.  Two jitted programs stand in for the serving programs:
``step`` (a Pallas kernel inside a layer scan, then an XLA reduction) and
``admit`` (one XLA matmul), compiled ahead of time and called directly
as the program store calls its programs.  Each call is wrapped in a host
``TraceAnnotation`` named like the harness's own spans and ends in a
blocking host read, with host sleeps between calls so the trace holds
idle gaps.  Writes ``fixture.xplane.pb`` and a text dump of every plane,
line and event (with stats) under ``--out``.
"""

from __future__ import annotations

import argparse
import glob
import shutil
import sys
import time
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=".cache/bench/fixture")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    if jax.devices()[0].platform != "tpu":
        print("record_trace_fixture: needs a TPU", file=sys.stderr)
        return 2
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    def scale_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def kernel(x):
        return pl.pallas_call(
            scale_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    def layer(h, w):
        return kernel(h @ w), None

    h = jnp.ones((8, 512), jnp.float32)
    ws = jnp.ones((4, 512, 512), jnp.float32) * 1e-3
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    # compiled ahead of time and called directly, as the program store does
    step = jax.jit(lambda h, ws: jax.lax.scan(layer, h, ws)[0].sum(axis=-1)
                   ).lower(h, ws).compile()
    admit = jax.jit(lambda a, b: (a @ b).max()).lower(a, a).compile()
    np.asarray(step(h, ws))
    np.asarray(admit(a, a))

    trace_dir = out / "raw"
    shutil.rmtree(trace_dir, ignore_errors=True)
    with jax.profiler.trace(str(trace_dir)):
        time.sleep(0.002)
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(6):
                with jax.profiler.TraceAnnotation("bench.decode_step"):
                    np.asarray(step(h, ws))
                if i % 2 == 0:
                    with jax.profiler.TraceAnnotation("bench.prefill_row"):
                        np.asarray(admit(a, a))
                time.sleep(0.002)
        # outside the window: must not count
        np.asarray(step(h, ws))
    pb = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)[0]
    shutil.copy(pb, out / "fixture.xplane.pb")

    pd = jax.profiler.ProfileData.from_file(str(out / "fixture.xplane.pb"))
    with open(out / "dump.txt", "w") as f:
        for plane in pd.planes:
            f.write(f"PLANE {plane.name} stats={list(plane.stats)}\n")
            for line in plane.lines:
                evs = list(line.events)
                f.write(f"  LINE {line.name!r} events={len(evs)}\n")
                for e in evs[:60]:
                    f.write(f"    {e.name!r} start={e.start_ns} "
                            f"dur={e.duration_ns} stats={list(e.stats)}\n")
    print((out / "dump.txt").read_text()[-20000:])
    print(f"device_kind={jax.devices()[0].device_kind!r} "
          f"memory_stats={jax.devices()[0].memory_stats()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
