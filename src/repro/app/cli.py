"""`python -m repro` — the single CLI for every workload.

    python -m repro train  --arch qwen2-0.5b --smoke --steps 20
    python -m repro serve  --arch qwen2-0.5b --smoke --continuous
    python -m repro trace  --out artifacts/megascan
    python -m repro dryrun --arch qwen3-14b --shape train_4k

Shared surface (every subcommand): ``--modules scan,metrics,scope,dpp,fbd``
toggles the module plugins (``none`` disables all), ``--set a.b=v``
applies dotted typed overrides onto the :class:`repro.app.config.RunConfig`,
``--config run.json`` layers a JSON file underneath them, and
``--trace-out`` exports the run's MegaScan events as a chrome trace —
uniformly, since serving and training emit the same ``TraceEvent``s.

Layering order (most specific last): dataclass defaults -> workload
defaults -> ``--config`` JSON -> ``--set`` overrides -> explicit flags.

This module imports neither jax nor any model code at import time: the
``dryrun`` workload must set ``XLA_FLAGS`` (via importing
``repro.launch.dryrun``) before the backend initialises.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys

from repro.app.config import build_run_config

# (flag, dest RunConfig path, argparse kwargs) — only flags the user actually
# passed are applied (argparse.SUPPRESS), so they override --config/--set
_SHARED = [
    ("--arch", "arch", dict(type=str)),
    ("--smoke", "smoke", dict(action="store_true")),
    ("--seed", "seed", dict(type=int)),
    ("--modules", "modules", dict(
        type=str, metavar="M1,M2",
        help="module plugins to attach (scan,metrics,ft,scope,fbd,dpp; "
             "'none' = off)")),
    ("--mesh", "mesh", dict(
        choices=("auto", "auto-mp", "host", "pod1", "pod2"))),
    ("--trace-out", "trace_out", dict(
        type=str, help="export this run's TraceEvents as a chrome trace "
                       "(a .jsonl path streams instead; non-.jsonl paths "
                       "also stream a .jsonl sidecar while running)")),
    ("--metrics-out", "obs.metrics_out", dict(
        type=str, help="stream the metrics registry as JSONL time series")),
    ("--compile-cache", "runtime.compile_cache", dict(
        type=str, metavar="DIR",
        help="persist AOT-compiled step executables under DIR, keyed on "
             "(model config, mesh, bucket, donation signature); a restarted "
             "process with the same config skips XLA compilation entirely")),
    ("--detect-online", "scan.detect_online", dict(
        action="store_true",
        help="run MegaScan's straggler detector over a sliding window of "
             "TraceEvents during the run (see --set scan.* thresholds)")),
]

_TRAIN = [
    ("--pp", "parallel.pp", dict(
        type=int, help="pipeline stages (>1 routes blocks through MegaDPP)")),
    ("--pp-schedule", "parallel.schedule", dict(
        choices=("1f1b", "dfc", "bfc", "wave"))),
    ("--n-micro", "parallel.n_micro", dict(
        type=int, help="pipeline microbatches per step (0 = 2*pp)")),
    ("--steps", "train.steps", dict(type=int)),
    ("--global-batch", "train.global_batch", dict(type=int)),
    ("--seq-len", "train.seq_len", dict(type=int)),
    ("--lr", "train.lr", dict(type=float)),
    ("--schedule", "train.schedule", dict(choices=("cosine", "wsd", "constant"))),
    ("--grad-accum", "train.grad_accum", dict(type=int)),
    ("--ckpt-dir", "train.ckpt_dir", dict(type=str)),
    ("--ckpt-every", "train.ckpt_every", dict(type=int)),
    ("--max-restarts", "ft.max_restarts", dict(
        type=int, help="bounded restarts for the supervised loop "
                       "(the ft module; see --set ft.* / ft.chaos.*)")),
    ("--chaos-crash-at", "ft.chaos.crash_at_step", dict(
        type=int, metavar="STEP",
        help="chaos: inject a real crash at this step (needs --ckpt-dir "
             "and the ft module; one of the --set ft.chaos.* knobs)")),
    ("--multi-pod", "mesh", dict(action="store_const", const="auto-mp")),
]

_SERVE = [
    ("--continuous", "serve.continuous", dict(action="store_true")),
    ("--batch", "serve.batch", dict(type=int)),
    ("--prompt-len", "serve.prompt_len", dict(type=int)),
    ("--max-new", "serve.max_new", dict(type=int)),
    ("--temperature", "serve.temperature", dict(type=float)),
    ("--requests", "serve.requests", dict(type=int)),
    ("--rate", "serve.rate", dict(type=float)),
    ("--slots", "serve.slots", dict(type=int)),
    ("--block-size", "serve.block_size", dict(type=int)),
    ("--num-blocks", "serve.num_blocks", dict(type=int)),
    ("--prompt-lens", "serve.prompt_lens", dict(type=str)),
    ("--decode-path", "serve.decode_path",
     dict(choices=("auto", "paged", "gathered"))),
    ("--prefill-path", "serve.prefill_path",
     dict(choices=("auto", "flash", "dense"),
          help="flash = the paged flash-prefill kernel (auto picks it "
               "where the Pallas kernel is real; dense one-shot otherwise)")),
    ("--spec-decode", "serve.spec_decode", dict(action="store_true")),
    ("--spec-k", "serve.spec_k", dict(type=int)),
    ("--drafter", "serve.drafter", dict(choices=("ngram", "random"))),
    ("--chunked-prefill", "serve.chunked_prefill", dict(
        action="store_true",
        help="stream long prompts chunk-by-chunk so decode interleaves")),
    ("--chunk-len", "serve.chunk_len", dict(
        type=int, help="prefill chunk length (0 = 2*block_size; must be a "
                       "multiple of block_size)")),
    ("--traffic", "serve.traffic", dict(
        choices=("poisson", "bursty", "diurnal"),
        help="arrival process for --continuous workloads")),
    ("--replicas", "router.replicas", dict(
        type=int, help="MegaRoute: front N engine replicas with a router")),
    ("--router-policy", "router.policy", dict(
        choices=("round_robin", "least_kv", "jsq"))),
    ("--prefill-replicas", "router.prefill_replicas", dict(
        type=int, help="disaggregate: first K replicas prefill-only, KV "
                       "migrates to the decode tier after the first token")),
    ("--slo-ttft", "router.slo_ttft_s", dict(
        type=float, help="SLO-aware admission: shed/redirect requests whose "
                         "estimated TTFT exceeds this (0 = off)")),
]

_TRACE = [
    ("--load", "trace.load", dict(type=str, help="analyse a JSONL trace")),
    ("--detect", "trace.detect", dict(
        type=str, metavar="TRACE",
        help="load a saved trace (chrome .json or streamed .jsonl), run "
             "align + detect, print the diagnosis summary")),
    ("--out", "trace.out", dict(type=str)),
    ("--slow-rank", "trace.slow_rank", dict(type=int)),
    ("--slow-factor", "trace.slow_factor", dict(type=float)),
    ("--dp", "trace.dp", dict(type=int)),
    ("--pp", "trace.pp", dict(type=int)),
    ("--tp", "trace.tp", dict(type=int)),
    ("--n-micro", "trace.n_micro", dict(type=int)),
    ("--iters", "trace.n_iters", dict(type=int)),
]

_DRYRUN = [
    ("--shape", "dryrun.shape", dict(type=str)),
    ("--all", "dryrun.all", dict(action="store_true")),
    ("--multi-pod", "dryrun.multi_pod", dict(choices=("off", "on", "both"))),
    ("--profile", "dryrun.profile", dict(type=str)),
    ("--grad-accum", "dryrun.grad_accum", dict(type=int)),
    ("--out", "dryrun.out", dict(type=str)),
    ("--save-hlo", "dryrun.save_hlo", dict(action="store_true")),
    ("--host-mesh", "dryrun.host_mesh", dict(
        action="store_true",
        help="compile on a small host mesh (CPU smoke) instead of 16x16")),
]

_WORKLOAD_FLAGS = {"train": _TRAIN, "serve": _SERVE, "trace": _TRACE,
                   "dryrun": _DRYRUN}


def _add_flags(ap: argparse.ArgumentParser, flags) -> None:
    # the dest encodes the RunConfig path ("train.steps" -> "train__steps");
    # build_run_config reverses the mapping
    for flag, path, kw in flags:
        ap.add_argument(flag, dest=path.replace(".", "__"),
                        default=argparse.SUPPRESS, **kw)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro",
        description="MegatronApp repro: one CLI for every workload; "
                    "module plugins toggle with --modules.",
    )
    sub = ap.add_subparsers(dest="workload", required=True)
    for wl, flags in _WORKLOAD_FLAGS.items():
        p = sub.add_parser(wl)
        p.add_argument("--config", default=None,
                       help="JSON RunConfig overlay (nested sections)")
        p.add_argument("--set", dest="sets", action="append", default=[],
                       metavar="KEY=VALUE",
                       help="dotted typed override, e.g. serve.spec_k=6")
        _add_flags(p, _SHARED)
        _add_flags(p, flags)
    return ap


def _parse(argv) -> tuple[str, "RunConfig"]:
    args = build_parser().parse_args(argv)
    workload = args.workload
    flag_overrides = {
        k: v for k, v in vars(args).items()
        if k not in ("workload", "config", "sets")
    }
    cfg = build_run_config(
        workload, config_json=args.config, sets=args.sets, **flag_overrides
    )
    return workload, cfg


def _print_results(results: dict) -> None:
    # plugin reports + workload metrics, JSON-ish, stable ordering
    drop = ("history",)  # printed by the workload itself
    view = {k: v for k, v in results.items() if k not in drop}
    if view:
        print(json.dumps(view, indent=1, default=str))


def run(argv: list[str]) -> dict:
    """Parse + run; returns ``session.results`` (tests use this directly)."""
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    workload, cfg = _parse(argv)

    if workload == "dryrun":
        # MUST precede any jax backend init: sets XLA_FLAGS (forced host
        # device count + SPMD dump dir) at module import
        import repro.launch.dryrun  # noqa: F401

    if workload == "train" and cfg.parallel.pp > 1:
        # pipeline meshes need pp*dp*tp devices; on a CPU-only host, force
        # the host platform to expose that many (inert on real fleets, and
        # a no-op if the user already set the flag).  Like dryrun, this must
        # precede backend init — nothing above imports jax.
        import os

        world = cfg.parallel.pp * cfg.parallel.dp * cfg.parallel.tp
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={world}"
            ).strip()

    from repro.app.session import Session

    try:
        session = Session(cfg)
        out = session.run()
    except (ValueError, KeyError) as e:
        # config/workload guards (unknown arch, wrong arch family, bad knob
        # combos) exit cleanly from the CLI instead of dumping a traceback
        msg = e.args[0] if e.args and isinstance(e.args[0], str) else str(e)
        raise SystemExit(msg) from e
    if workload == "dryrun":
        failed = [t for t, v in out.items() if "error" in v]
        if failed:
            raise SystemExit(f"{len(failed)} cell(s) failed: {failed}")

    if workload == "train":
        _, history = out
        for h in history:
            print(f"step {h['step']:>5}  loss {h['loss']:.4f}  "
                  f"lr {h.get('lr', 0):.2e}")
    elif workload == "serve":
        met = session.results.get("serve_metrics", {})
        if cfg.serve.continuous:
            outs, _ = out
            sc = session.results.get("serve_config", {})
            routed = sc.get("replicas", 1) > 1 or sc.get("policy")
            print(f"arch={session.model_cfg.name} continuous "
                  f"slots={sc.get('num_slots', cfg.serve.slots)} "
                  f"blocks={sc.get('num_blocks')}x{sc.get('block_size')} "
                  f"requests={len(outs)} "
                  f"decode_path={session.results.get('decode_path')}"
                  + (f" spec_k={cfg.serve.spec_k} drafter={cfg.serve.drafter}"
                     if cfg.serve.spec_decode else "")
                  + (f" replicas={sc.get('replicas')}"
                     f" policy={sc.get('policy')}" if routed else "")
                  + (f" traffic={sc.get('traffic')}"
                     if sc.get("traffic", "poisson") != "poisson" else ""))
            keys = ["generated_tokens", "wall_s", "tokens_per_s",
                    "ttft_p50_s", "ttft_p99_s", "queue_wait_p50_s",
                    "queue_wait_p99_s", "latency_p50_s",
                    "latency_p99_s", "preemptions", "steps"]
            if routed:
                keys += ["shed", "shed_rate", "redirects", "migrations",
                         "placed_per_replica", "replica_tokens", "load_skew"]
            if cfg.serve.spec_decode:
                keys += ["spec_proposed", "spec_accepted", "spec_accept_rate"]
            for k in keys:
                v = met.get(k)
                print(f"  {k:16s} {v:.4f}" if isinstance(v, float)
                      else f"  {k:16s} {v}")
            for rid in list(outs)[:2]:
                print(f"  req {rid}: {outs[rid][:12]}...")
        else:
            gen, _ = out
            s = cfg.serve
            print(f"arch={cfg.arch} batch={s.batch} prompt={s.prompt_len} "
                  f"new={s.max_new}")
            print(f"prefill: {met['prefill_s']*1e3:.1f} ms "
                  f"({met['prefill_tok_s']:.0f} tok/s)")
            print(f"decode : {met['decode_s']*1e3:.1f} ms "
                  f"({met['decode_tok_s']:.0f} tok/s)")
            for b in range(min(s.batch, 2)):
                print(f"  seq {b}: {[int(t) for t in gen[b][:12]]}...")
    elif workload == "trace":
        print(json.dumps(session.results.get("diagnosis", {}), indent=1))
        if "truth" in session.results:
            t = session.results["truth"]
            print(f"slow-rank detection: "
                  f"{'CORRECT' if t['detected'] else 'MISMATCH'} "
                  f"(truth={t['slow_ranks']})")
    _print_results({k: v for k, v in session.results.items()
                    if k in ("scan", "metrics", "ft", "scope", "fbd", "dpp",
                             "parallel", "trace_out")})
    return session.results


def main(argv: list[str] | None = None) -> None:
    from repro.core.compile_cache import use_jax_cache

    use_jax_cache()
    run(sys.argv[1:] if argv is None else list(argv))


if __name__ == "__main__":
    main()
