#!/usr/bin/env python3
"""A traced run of a serving cell, read with the program's MegaScan spans.

    python3 bench/tools/megascan_trace.py --workload serve-qwen2-0.5b-chat \
        --seed 7 --seconds 51 --out out/megascan

Runs the cell as ``bench/run.py --trace 1`` does and keeps its profiler
trace under ``--out``.  The last line of standard output is one JSON object:
``run``, the result line ``bench/run.py`` prints; ``megascan``, what the
program's ``megascan.*`` spans and the chip's ``XLA Modules`` line give:
the median host self time of a tick that called the device
(``tick_host_ms``), the median device time of one decode program
(``decode_step_device_ms``) and of its part outside the paged attention
kernel (``decode_step_nonattn_ms``), the device's idle time by the
innermost host span, benchmark spans and ``megascan.*`` spans together
(``idle_gaps``), and each span's count and summed time.  With
``--span-cost N`` it first times N MegaScan scopes and N bare profiler spans
on this host, with the profiler off and on (``span_cost_us``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def bench_run():
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span_cost(n: int) -> dict:
    """Microseconds per scope (``scope``: the whole MegaScan scope, with its
    ``TraceEvent``) and per profiler span alone (``annotate``), with the
    profiler off and on."""
    import jax

    from benchlib.serve import trace_options
    from repro.core.tracing.tracer import Tracer

    tr = Tracer(rank=0)

    def scope():
        for i in range(n):
            with tr.scope("decode", kind="compute", step=i, active=64,
                          tokens=64):
                pass

    def annotate():
        for i in range(n):
            with tr.annotate("decode", step=i, active=64, tokens=64):
                pass

    def timed(fn) -> float:
        tr.clear()
        t = time.perf_counter()
        fn()
        return (time.perf_counter() - t) / n * 1e6

    out = {"n": n, "scope_off": timed(scope), "annotate_off": timed(annotate)}
    with tempfile.TemporaryDirectory(prefix="span-cost-") as d:
        jax.profiler.start_trace(d, profiler_options=trace_options())
        try:
            out["scope_on"] = timed(scope)
            out["annotate_on"] = timed(annotate)
        finally:
            jax.profiler.stop_trace()
    tr.clear()
    return out


def span_totals(sp) -> dict:
    by: dict[str, list] = {}
    for s in sp.host:
        c = by.setdefault(s.name, [0, 0.0])
        c[0] += 1
        c[1] += (s.end - s.start) / 1e9
    return {k: {"count": c, "s": t} for k, (c, t) in sorted(by.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True, help="directory for the trace")
    ap.add_argument("--span-cost", type=int, default=0)
    args = ap.parse_args(argv)

    run = bench_run()
    from benchlib import megascan, xplane
    from benchlib.device import peak, require_tpu
    from benchlib.spec import load_cell

    cell = load_cell(args.workload)
    devs = require_tpu(cell.chips)
    pk = peak(devs[0].device_kind)
    run.compile_cache()
    cost = span_cost(args.span_cost) if args.span_cost else None
    out_dir = Path(args.out) / f"seed{args.seed}"
    out_dir.mkdir(parents=True, exist_ok=True)
    res = run.runner(cell.traffic["kind"]).run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=True,
        t_start=T_START, devs=devs, peak=pk, trace_root=str(out_dir))
    line = run.assemble(cell, res, True)
    tr = res["per_layer"]["trace"]
    sp = megascan.load(xplane.find_xplane(str(out_dir)))
    both = xplane.Trace(devices=tr.devices,
                        host=sorted(tr.host + sp.host, key=lambda o: o.start))
    t0, t1 = xplane.extent(tr)
    found = {
        "tick_host_ms": megascan.tick_host_ms(sp),
        "decode_step_device_ms": megascan.module_ms(sp, "jit_serve_decode"),
        "decode_step_nonattn_ms": megascan.module_outside_ms(
            sp, tr.devices, "jit_serve_decode", "paged_attention_pallas"),
        "prefill_step_device_ms": megascan.module_ms(sp, "jit_serve_prefill"),
        "idle_gaps": xplane.idle_gaps(both, t0, t1),
        "spans": span_totals(sp),
        "span_cost_us": cost,
    }
    doc = {"run": line, "megascan": found}
    (out_dir / "result.json").write_text(json.dumps(doc, indent=1))
    print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
