#!/usr/bin/env python3
"""Find the highest request rate a serving cell sustains: one set-up per pool
size, then a window at each rate, in one process on the chip.

    python3 bench/tools/knee_sweep.py --workload serve-qwen2-0.5b-chat \
        --rates 1,1.5,2 --seconds 30 [--num-blocks 1025,2049] \
        [--look out/trace_look.json]

For each pool size and rate it prints one JSON line: the tails, tokens per
second, how long past the close the due requests took to finish, the median
queue wait of the first and the last third of the requests, the mean device
call times of decode and prefill, and the preemptions.  A backlog that grows
shows as a late finish and a queue wait that rises from the first third to
the last.  A backlog grows where the due requests are not all served within
``--late`` seconds of the close, or where the last third's median queue wait
is more than ``--grow-ms`` above the first third's.  The sweep of a pool
stops at the first such rate, and a last line gives the knee, the highest
rate before it.  ``--look`` also
traces a few seconds of the first rate and writes a summary of the trace's planes, lines and busiest op names.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import tempfile
import time
from pathlib import Path
from statistics import median

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    sys.path.insert(0, str(p))


def hist(reg, name: str) -> tuple[float, int]:
    v = reg.snapshot().get(name) if reg is not None else None
    return (v["sum"], v["count"]) if isinstance(v, dict) else (0.0, 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--num-blocks", default="",
                    help="pool sizes to sweep (default: the configuration's)")
    ap.add_argument("--look", default="")
    ap.add_argument("--late", type=float, default=20.0,
                    help="seconds past the close to wait for due requests")
    ap.add_argument("--grow-ms", type=float, default=1000.0,
                    help="rise in median queue wait that counts as a growing backlog")
    args = ap.parse_args(argv)

    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_run", ROOT / "bench/run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from benchlib import serve, xplane
    from benchlib.device import require_tpu
    from benchlib.spec import load_cell
    from benchlib.traffic import serve_requests

    run.compile_cache()
    devs = require_tpu(1)
    cell = load_cell(args.workload)
    pools = ([int(n) for n in args.num_blocks.split(",")] if args.num_blocks
             else [int(cell.config["kv_pool_blocks"])])
    traffic = cell.traffic
    for nb in pools:
        knee = None
        c = dataclasses.replace(cell, config=dict(cell.config, kv_pool_blocks=nb))
        t0 = time.perf_counter()
        session, srv, dense, spans = serve.build(c, args.seed,
                                                 trace=bool(args.look))
        print(f"num_blocks {nb}: set-up {time.perf_counter() - t0:.2f} s on "
              f"{devs[0].device_kind}", file=sys.stderr, flush=True)
        reg = session.metrics_registry
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            reqs = serve_requests(dict(traffic, rate_per_s=rate),
                                  args.seed + i, args.seconds, dense.vocab)
            look = args.look and i == 0 and nb == pools[0]
            tdir = tempfile.mkdtemp(prefix="knee-trace-") if look else None
            d0, p0 = hist(reg, "serve.decode_step_s"), hist(reg, "serve.prefill_s")
            win = serve.run_window(session, srv, reqs, args.seconds,
                                   trace_dir=tdir,
                                   trace_from=args.seconds / 2 - 2,
                                   trace_len=4.0, spans=spans,
                                   late_s=args.late)
            done = win.t_done
            e2e, attempted, failed = serve.end_to_end(srv, win, done)
            d1, p1 = hist(reg, "serve.decode_step_s"), hist(reg, "serve.prefill_s")
            rs = sorted(srv.sched.requests.values(), key=lambda r: r.arrival)
            third = max(len(rs) // 3, 1)
            qw = [r.t_admitted - r.arrival for r in rs if r.t_admitted is not None]
            dn, pn = max(d1[1] - d0[1], 1), max(p1[1] - p0[1], 1)
            row = {"num_blocks": nb, "rate": rate, "requests": attempted,
                   "failed": failed,
                   "finish_past_close_s": done - args.seconds,
                   "queue_wait_p50_first_third_ms": median(qw[:third]) * 1e3 if qw else None,
                   "queue_wait_p50_last_third_ms": median(qw[-third:]) * 1e3 if qw else None,
                   "decode_calls": d1[1] - d0[1],
                   "decode_ms_mean": (d1[0] - d0[0]) / dn * 1e3,
                   "prefill_calls": p1[1] - p0[1],
                   "prefill_ms_mean": (p1[0] - p0[0]) / pn * 1e3,
                   "preemptions": sum(r.n_preemptions for r in rs),
                   "compiles_in_window": win.compiles, **e2e}
            growing = (not srv.sched.all_done or not qw or median(qw[-third:])
                       - median(qw[:third]) > args.grow_ms / 1e3)
            row["growing"] = growing
            print(json.dumps(row), flush=True)
            if look:
                path = xplane.find_xplane(tdir)
                Path(args.look).parent.mkdir(parents=True, exist_ok=True)
                Path(args.look).write_text(json.dumps(xplane.describe(path), indent=1))
                tr = xplane.load(path, host_names={"MegaServe.step", "prefill", "decode"})
                print(json.dumps({"busy_s": xplane.busy_s(tr),
                                  "window_s": win.trace_t1 - win.trace_t0,
                                  "top_ops": [[n[:160], s] for n, s in xplane.top_ops(tr, 15)],
                                  "idle": xplane.idle_gaps(tr, *xplane.extent(tr)),
                                  "decode_calls": len(spans.decode_kv),
                                  "prefill_calls": len(spans.prefill_n)}),
                      flush=True)
                spans.decode_kv.clear()
                spans.prefill_n.clear()
            if growing:
                break   # a higher rate only grows the backlog faster
            knee = rate
        print(json.dumps({"num_blocks": nb, "knee": knee}), flush=True)
        srv.pool = None
        del srv, session
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
