#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``) names a configuration
(``bench/configs/``) and a traffic mix or job (``bench/traffic/``), whose
``kind`` picks the runner.  With ``--trace 0`` the run reports the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace by the readers in ``bench/metrics/``.  Both check the timed
path's output against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (platform, kind, count,
memory peak; busy and window seconds when traced), ``breakdown`` when traced,
and last ``checks``, each compared number beside its limit.  The same
numbers end standard error.  Where JAX finds no TPU, or fewer chips than the
cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchlib.device import NoAccelerator, peak, require_tpu  # noqa: E402
from benchlib.spec import load_cell, metric_reader  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set, else
    ``<checkout>/.jax_cache``; every executable is kept, however quick."""
    import jax

    from repro.core.compile_cache import use_jax_cache

    path = use_jax_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def runner(kind: str):
    import importlib

    return importlib.import_module(f"benchlib.{kind}")


def finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def assemble(cell, res: dict, trace: bool, root: Path = ROOT) -> dict:
    metrics = {}
    if trace:
        rec = res["per_layer"]
        for m in cell.per_layer:
            v = metric_reader(m["name"], root)(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = res["end_to_end"]
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": res["device"]}
    if trace and "breakdown" in res:
        out["breakdown"] = res["breakdown"]
    out["checks"] = {k: {"value": finite(v["value"]), "limit": v["limit"]}
                     for k, v in res["checks"].items()}
    return out


def run(args, *, root: Path = ROOT, require_chip: bool = True,
        peak_override=None) -> dict:
    """One run.  ``require_chip=False`` (tests only) skips the look for a TPU
    and the persistent cache, and takes ``peak_override`` for the peaks."""
    cell = load_cell(args.workload, root)
    import jax

    if require_chip:
        devs = require_tpu(cell.chips)
        pk = peak(devs[0].device_kind)
        compile_cache()
    else:
        devs = jax.devices()[: cell.chips]
        pk = peak_override
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}",
          file=sys.stderr, flush=True)
    trace_root = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else ""
    try:
        res = runner(cell.traffic["kind"]).run_cell(
            cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            t_start=T_START, devs=devs, peak=pk, trace_root=trace_root)
    finally:
        if trace_root:
            shutil.rmtree(trace_root, ignore_errors=True)
    return assemble(cell, res, bool(args.trace), root)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args)
    except NoAccelerator as e:
        print(f"bench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
