#!/usr/bin/env python3
"""Readings that set the DeepSeek-V2-Lite cell's rate and limit, in one
process on the chip: the knee sweep, then the program's and the float8
control's mean logit gaps.

    python3 bench/tools/latent_moe_probe.py \
        --workload serve-deepseek-v2-lite-ep8-chat \
        --rates 2,3.5,5 --seconds 45 --late 90 \
        --control-seeds 11,12,13 --control-rate 2 --control-seconds 10

One set-up (``benchlib.serve_latent_moe.build``).  Each rate offers the
cell's traffic for ``--seconds`` and waits at most ``--late`` past the close,
and prints one JSON line as ``knee_sweep.py`` does (tails, tokens per second,
finish past the close, median queue wait of the first and last thirds, mean
device call times); the sweep stops at the first rate whose backlog grows,
and a line gives the knee.  Then, for each control seed, the weights are made
anew (the compiled steps are kept), a short window runs at
``--control-rate``, the pool is freed, and the sample a benchmark run would
judge is judged twice against the float32 reference: the served tokens (the
program's reading) and the float8 control's first choices at the same
positions (the control's).  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path
from statistics import median

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    sys.path.insert(0, str(p))


def hist(reg, name: str) -> tuple[float, int]:
    v = reg.snapshot().get(name) if reg is not None else None
    return (v["sum"], v["count"]) if isinstance(v, dict) else (0.0, 0)


def window_row(srv, reg, win, rate, seconds, grow_ms, d0, p0) -> dict:
    from benchlib import serve

    e2e, attempted, failed = serve.end_to_end(srv, win, win.t_done)
    d1, p1 = hist(reg, "serve.decode_step_s"), hist(reg, "serve.prefill_s")
    rs = sorted(srv.sched.requests.values(), key=lambda r: r.arrival)
    third = max(len(rs) // 3, 1)
    qw = [r.t_admitted - r.arrival for r in rs if r.t_admitted is not None]
    dn, pn = max(d1[1] - d0[1], 1), max(p1[1] - p0[1], 1)
    row = {"rate": rate, "requests": attempted, "failed": failed,
           "finish_past_close_s": win.t_done - seconds,
           "queue_wait_p50_first_third_ms": median(qw[:third]) * 1e3 if qw else None,
           "queue_wait_p50_last_third_ms": median(qw[-third:]) * 1e3 if qw else None,
           "decode_calls": d1[1] - d0[1],
           "decode_ms_mean": (d1[0] - d0[0]) / dn * 1e3,
           "prefill_calls": p1[1] - p0[1],
           "prefill_ms_mean": (p1[0] - p0[0]) / pn * 1e3,
           "preemptions": sum(r.n_preemptions for r in rs),
           "compiles_in_window": win.compiles, **e2e}
    row["growing"] = bool(failed or not qw or median(qw[-third:])
                          - median(qw[:third]) > grow_ms / 1e3)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--late", type=float, default=90.0)
    ap.add_argument("--grow-ms", type=float, default=1000.0)
    ap.add_argument("--seed", type=int, default=4294967311)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control-rate", type=float, default=1.0)
    ap.add_argument("--control-seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import importlib.util

    import jax
    import numpy as np

    spec = importlib.util.spec_from_file_location("bench_run", ROOT / "bench/run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from benchlib import serve
    from benchlib import serve_latent_moe as lm_run
    from benchlib.device import memory_peak_bytes, require_tpu
    from benchlib.latent_moe_reference import served_gaps
    from benchlib.spec import load_cell
    from benchlib.traffic import serve_requests

    run.compile_cache()
    devs = require_tpu(1)
    cell = load_cell(args.workload)
    t0 = time.perf_counter()
    session, srv, m, _ = lm_run.build(cell, args.seed, trace=False)
    print(f"set-up {time.perf_counter() - t0:.2f} s on {devs[0].device_kind}; "
          f"memory peak {memory_peak_bytes(devs)}", file=sys.stderr, flush=True)
    reg = session.metrics_registry
    traffic = cell.traffic
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",") if r):
        reqs = serve_requests(dict(traffic, rate_per_s=rate), args.seed + i,
                              args.seconds, m.vocab)
        d0, p0 = hist(reg, "serve.decode_step_s"), hist(reg, "serve.prefill_s")
        win = serve.run_window(session, srv, reqs, args.seconds,
                               late_s=args.late)
        row = window_row(srv, reg, win, rate, args.seconds, args.grow_ms, d0, p0)
        print(json.dumps(row), flush=True)
        if row["growing"]:
            break
        knee = rate
    if args.rates:
        print(json.dumps({"knee": knee}), flush=True)
    if not srv.sched.all_done:
        srv.drain()          # what the last (growing) window left behind

    pool_shapes = jax.tree.map(lambda a: (a.shape, a.dtype), srv.pool)
    n_sample = int(traffic["check"]["sample_requests"])
    for seed in (int(s) for s in args.control_seeds.split(",") if s):
        srv.params = None
        gc.collect()
        srv.params = lm_run.make_weights(m, seed, cell.config["as_run"]["serve_dtype"])
        reqs = serve_requests(dict(traffic, rate_per_s=args.control_rate), seed,
                              args.control_seconds, m.vocab)
        win = serve.run_window(session, srv, reqs, args.control_seconds,
                               late_s=args.late)
        prompts = {r.rid: r.prompt for r in reqs}
        fin = [(prompts[r.rid], list(r.generated))
               for r in srv.sched.requests.values()
               if r.t_finished is not None and len(r.generated) == r.max_new]
        srv.pool = None
        gc.collect()
        prog, ctl = [], []
        for j in serve.sample(fin, seed, n_sample):
            g, c = served_gaps(srv.params, m, *fin[j], control=True)
            prog.append(g)
            ctl.append(c)
        prog, ctl = np.concatenate(prog), np.concatenate(ctl)
        print(json.dumps({
            "seed": seed, "requests": len(reqs), "finished": len(fin),
            "served_past_close_s": win.t_done - args.control_seconds,
            "program_mean_gap": float(prog.mean()),
            "program_widest_gap": float(prog.max()),
            "control_mean_gap": float(ctl.mean()),
            "control_widest_gap": float(ctl.max()),
            "tokens": int(prog.size)}), flush=True)
        srv.pool = jax.tree.map(lambda s: jax.numpy.zeros(*s), pool_shapes,
                                is_leaf=lambda t: isinstance(t, tuple))
    return 0


if __name__ == "__main__":
    sys.exit(main())
