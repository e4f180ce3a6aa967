#!/usr/bin/env python3
"""Readings that set a serving cell's limit: the program's mean logit gap
and the float8 control's, seed by seed, in one process on the chip.

    python3 bench/tools/control.py --workload serve-qwen2-0.5b-chat \
        --seeds 11,12,13 --seconds 10

For each seed the weights are made anew (the compiled steps are kept: the
shapes do not change), the cell's traffic runs for a short window at the
cell's own rate and is served to the end, and the same sample that a
benchmark run draws (its longest request and others from the seed) is judged
twice against the float32 reference: the served tokens (the program's
reading) and, at each of the same positions, the token the float8 control
puts first (the control's reading).  One JSON line per seed.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    sys.path.insert(0, str(p))


def readings(srv, reqs, weights, dense, seed: int, n_sample: int) -> dict:
    """The program's and the control's mean (and widest) logit gap over the
    sample a benchmark run would judge."""
    import numpy as np

    from benchlib.reference import served_gaps
    from benchlib.serve import sample

    prompts = {r.rid: r.prompt for r in reqs}
    fin = [(prompts[r.rid], list(r.generated)) for r in srv.sched.requests.values()
           if r.t_finished is not None and len(r.generated) == r.max_new]
    prog, ctrl = [], []
    for i in sample(fin, seed, n_sample):
        g, c = served_gaps(weights, dense, fin[i][0], fin[i][1], control=True)
        prog.append(g)
        ctrl.append(c)
    prog, ctrl = np.concatenate(prog), np.concatenate(ctrl)
    return {"program_mean_gap": float(prog.mean()),
            "control_mean_gap": float(ctrl.mean()),
            "program_widest_gap": float(prog.max()),
            "control_widest_gap": float(ctrl.max()),
            "sampled_tokens": int(prog.size), "finished": len(fin),
            "due": len(reqs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    import importlib.util

    import jax

    spec = importlib.util.spec_from_file_location("bench_run", ROOT / "bench/run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from benchlib import serve
    from benchlib.device import require_tpu
    from benchlib.spec import load_cell
    from benchlib.traffic import serve_requests
    from benchlib.weights import make_weights

    run.compile_cache()
    require_tpu(1)
    cell = load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    session, srv, dense, _ = serve.build(cell, seeds[0], trace=False)
    n_sample = int(cell.traffic["check"]["sample_requests"])
    for seed in seeds:
        t0 = time.perf_counter()
        srv.params = make_weights(dense, seed, cell.config["as_run"]["serve_dtype"])
        reqs = serve_requests(cell.traffic, seed, args.seconds, dense.vocab)
        serve.run_window(session, srv, reqs, args.seconds,
                         late_s=float(cell.traffic["check"].get("late_s", serve.LATE_S)))
        if not srv.sched.all_done:
            srv.drain()
        row = {"seed": seed, **readings(srv, reqs, srv.params, dense, seed, n_sample),
               "s": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        jax.block_until_ready(srv.params)
    return 0


if __name__ == "__main__":
    sys.exit(main())
