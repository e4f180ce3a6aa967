#!/usr/bin/env python3
"""Record the small profiler trace that ``bench/tests`` checks the trace
reduction against, on the chip.

    python3 bench/tools/record_probe_trace.py --out out/probe_trace

Inside one traced window, under the host span ``probe``: three matmuls of
4096 x 4096 in bfloat16, each followed by a 30 ms sleep of the host (the
chip idles), then three calls of a Pallas kernel named ``probe_kernel``.
It prints what the reduction reads from the trace, for the test's
expectations.  Only device ops and annotations are recorded, so the file
stays small.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from benchlib import xplane
    from benchlib.device import require_tpu
    from benchlib.serve import trace_options

    require_tpu(1)

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    kernel = jax.jit(pl.pallas_call(
        double, out_shape=jax.ShapeDtypeStruct((1024, 1024), jnp.float32),
        name="probe_kernel"))
    mm = jax.jit(lambda a: a @ a)
    a = jnp.ones((4096, 4096), jnp.bfloat16)
    x = jnp.ones((1024, 1024), jnp.float32)
    jax.block_until_ready((mm(a), kernel(x)))          # compile outside
    jax.profiler.start_trace(args.out, profiler_options=trace_options())
    with jax.profiler.TraceAnnotation("probe"):
        for _ in range(3):
            jax.block_until_ready(mm(a))
            with jax.profiler.TraceAnnotation("probe_sleep"):
                time.sleep(0.03)
        for _ in range(3):
            jax.block_until_ready(kernel(x))
    jax.profiler.stop_trace()
    path = xplane.find_xplane(args.out)
    tr = xplane.load(path, host_names={"probe", "probe_sleep"})
    t0, t1 = xplane.extent(tr)
    print(json.dumps({
        "path": path, "bytes": Path(path).stat().st_size,
        "devices": tr.n_devices, "busy_s": xplane.busy_s(tr),
        "extent_s": (t1 - t0) / 1e9,
        "probe_kernel_s": xplane.op_seconds(tr, "probe_kernel"),
        "probe_kernel_n": xplane.op_count(tr, "probe_kernel"),
        "top_ops": xplane.top_ops(tr, 5),
        "idle_gaps": xplane.idle_gaps(tr, t0, t1),
        "host": [[h.name, (h.end - h.start) / 1e9] for h in tr.host],
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
