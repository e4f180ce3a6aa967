"""Session: the one runtime object behind every `python -m repro` workload.

A Session owns the pieces every entry point used to re-roll by hand:

* the **model config** (``get_config(arch, smoke=...)``),
* the **mesh** (shared auto/host/pod selection) and the architecture's
  **sharding rules** (installed via ``parallel.sharding.axis_rules``),
* the **module plugins** (MegaScan / MegaScope / MegaFBD / MegaDPP), each
  attached through the uniform :class:`repro.app.plugins.ModulePlugin`
  surface,
* the shared **chrome-trace export** (``run_cfg.trace_out`` works for every
  workload, not just training).

Tracing is on by default for every workload (the ``scan`` module is in the
default module set) — the documented unification of the old split where
``train()`` silently disabled its tracer while ``MegaServe`` enabled it.
Pass ``--modules none`` (or build a Session with ``modules=()``) to opt out.

Workloads: ``train`` (the jitted train loop), ``serve`` (MegaServe
continuous batching or the static lockstep baseline), ``trace`` (offline
MegaScan: simulate/load -> align -> detect), ``dryrun`` (compile-analysis
cells; see ``repro.launch.dryrun`` for the XLA-flags ordering caveat).
"""

from __future__ import annotations

import json
import logging
import time
from contextlib import nullcontext
from pathlib import Path
from typing import Any, Callable

from repro.app.config import RunConfig
from repro.app.plugins import ModulePlugin, build_plugins

log = logging.getLogger("repro.app")


def pick_mesh(spec: str):
    """Shared mesh selection (was private to the train launcher).

    ``auto`` picks the largest production mesh the device fleet provides,
    else a host mesh; ``auto-mp`` prefers the two-pod shape; ``host`` /
    ``pod1`` / ``pod2`` force a shape.
    """
    import jax

    from repro.launch.mesh import make_host_mesh, make_production_mesh

    n = len(jax.devices())
    if spec == "host":
        return make_host_mesh()
    if spec == "pod1":
        return make_production_mesh(multi_pod=False)
    if spec == "pod2":
        return make_production_mesh(multi_pod=True)
    if spec in ("auto", "auto-mp"):
        if spec == "auto-mp" and n >= 512:
            return make_production_mesh(multi_pod=True)
        if n >= 256:
            return make_production_mesh(multi_pod=False)
        return make_host_mesh()
    raise ValueError(f"unknown mesh spec {spec!r}")


class Session:
    """One configured run: plugins + mesh + config, with a uniform lifecycle.

    >>> s = Session(RunConfig.for_workload("train", arch="qwen2-0.5b",
    ...                                    smoke=True))
    >>> state, history = s.run()         # doctest: +SKIP
    >>> s.results["scan"]["events"]      # doctest: +SKIP

    ``run()`` dispatches on ``run_cfg.workload``, then finalizes every
    plugin (reports land in ``session.results``) and exports the chrome
    trace when ``run_cfg.trace_out`` is set.
    """

    def __init__(
        self,
        run_cfg: RunConfig,
        plugins: list[ModulePlugin] | None = None,
        *,
        model_cfg=None,
    ):
        from repro.core.tracing.tracer import Tracer
        from repro.models.hooks import NULL_COLLECTOR

        self.run_cfg = run_cfg
        # an explicit ModelConfig (e.g. an unregistered preset) wins over
        # the arch-registry lookup
        self.model_cfg = model_cfg
        if model_cfg is None and run_cfg.arch:
            from repro.configs import get_config

            self.model_cfg = get_config(run_cfg.arch, smoke=run_cfg.smoke)
        # plugin-claimable resources, with inert defaults: no scan plugin ->
        # disabled tracer, no scope plugin -> null collector, no metrics
        # plugin -> no registry (instrumented loops skip publication)
        self.tracer = Tracer(rank=0, enabled=False)
        self.collector = NULL_COLLECTOR
        self.metrics_registry = None
        # no ft plugin -> no controller (the train loop runs unsupervised);
        # detection listeners receive every online DetectionUpdate the scan
        # plugin's detector produces
        self.ft_controller = None
        # runtime.compile_cache -> persistent executable store shared by the
        # train loop's AOT step and the serving engines' precompile ladders
        # (MegaServe.from_session / Router.from_session pick it up by name)
        self.compile_cache = None
        if run_cfg.runtime.compile_cache:
            from repro.core.compile_cache import CompileCache

            self.compile_cache = CompileCache(run_cfg.runtime.compile_cache)
        self.detection_listeners: list[Callable] = []
        self.results: dict[str, Any] = {}
        self.plugins = (
            plugins if plugins is not None
            else build_plugins(run_cfg.modules, run_cfg)
        )
        for p in self.plugins:
            p.setup(self)
        self._finalized = False

    # ------------------------------------------------------------ plumbing
    def mesh(self):
        return pick_mesh(self.run_cfg.mesh)

    def sharding_rules(self, shape_kind: str):
        from repro.parallel.profiles import rules_for

        return rules_for(self.model_cfg, shape_kind)

    def wrap_step(self, step_fn: Callable) -> Callable:
        for p in self.plugins:
            step_fn = p.wrap_step(step_fn)
        return step_fn

    def notify_step(self, events, metrics) -> None:
        # the hooks of a step that traced something get a ``plugins`` host
        # span; an idle serving tick (no events) adds none
        span = (self.tracer.scope("plugins", kind="host") if events
                else nullcontext())
        with span:
            for p in self.plugins:
                p.on_step(self, events, metrics)

    def notify_detection(self, update) -> None:
        """Fan one online ``DetectionUpdate`` out to detection listeners
        (the ft controller registers here) — called by the scan plugin."""
        for listener in self.detection_listeners:
            listener(update)

    def step_hooks(self):
        from repro.train.loop import StepHooks

        return StepHooks(wrap_step=self.wrap_step, on_step=self.notify_step)

    def finalize(self) -> dict[str, Any]:
        """Run every plugin's finalize once; export the shared chrome trace."""
        if self._finalized:
            return self.results
        self._finalized = True
        for p in self.plugins:
            self.results[p.name] = p.finalize(self)
        if self.run_cfg.trace_out:
            # an explicit --trace-out always writes, even when the run
            # traced nothing (e.g. --modules none) — an empty trace file
            # is debuggable, a silently missing one is not
            if not self.tracer.events:
                log.warning(
                    "trace_out=%s: no TraceEvents were recorded (is the "
                    "'scan' module enabled?)", self.run_cfg.trace_out)
            out_path = Path(self.run_cfg.trace_out)
            streamed = self.results.get("scan", {}).get("stream", "")
            if out_path.suffix == ".jsonl":
                # a .jsonl trace_out asks for the streaming format itself;
                # the scan plugin already wrote it incrementally — only
                # dump at the end when no plugin streamed (--modules none)
                if str(out_path) != streamed:
                    with open(out_path, "w") as f:
                        for e in self.tracer.events:
                            f.write(json.dumps(e.to_json()) + "\n")
            else:
                from repro.core.tracing.chrome import save_chrome

                save_chrome(self.tracer.events, self.run_cfg.trace_out)
            self.results["trace_out"] = self.run_cfg.trace_out
            log.info("trace -> %s", self.run_cfg.trace_out)
        return self.results

    # ----------------------------------------------------------- dispatch
    def run(self):
        """Run the configured workload, then finalize plugins."""
        fn = {
            "train": self.train,
            "serve": self.serve,
            "trace": self.trace,
            "dryrun": self.dryrun,
        }[self.run_cfg.workload]
        try:
            return fn()
        finally:
            self.finalize()

    # -------------------------------------------------------------- train
    def _rank_event_spec(self, plan=None):
        """Resolve the ``obs`` section into a per-rank event synthesis spec
        (``None`` unless rank events or straggler induction are asked for).

        With a composed ``ParallelPlan`` the synthesized topology follows the
        plan's real (dp, pp, tp) — so detector rank coordinates and the ft
        mitigation's link-axis routing agree with the mesh actually training
        — and the ``obs`` section's dims only apply to plan-less runs."""
        o = self.run_cfg.obs
        ch = self.run_cfg.ft.chaos
        chaos_needs = self.ft_controller is not None and (
            ch.slow_rank_from >= 0 or bool(ch.degrade_link)
        )
        if not (o.rank_events or o.slow_rank >= 0 or chaos_needs):
            return None
        from repro.obs import RankEventSpec

        dims = (
            {"dp": plan.dp, "pp": plan.pp, "tp": plan.tp}
            if plan is not None else {"dp": o.dp, "pp": o.pp, "tp": o.tp}
        )
        return RankEventSpec(
            **dims,
            slow_rank=o.slow_rank, slow_factor=o.slow_factor,
        )

    def _train_derived(self):
        """Resolve the 0-means-auto training fields against smoke/full."""
        rc, t = self.run_cfg, self.run_cfg.train
        seq = t.seq_len or (128 if rc.smoke else 4096)
        batch = t.global_batch or (8 if rc.smoke else 256)
        # minicpm trains with WSD per its paper (kept from the old launcher)
        schedule = t.schedule
        if self.model_cfg.name.startswith("minicpm") and schedule == "cosine":
            schedule = "wsd"
        return seq, batch, schedule

    def parallel_plan(self):
        """Resolve the ``parallel`` section into a ``ParallelPlan`` (pp>1) or
        ``None`` (the plain DP/TP path).  Wave resolution — ``schedule=wave``
        with ``wave=0`` — runs MegaDPP's planner under the ``dpp`` section's
        memory cap."""
        par = self.run_cfg.parallel
        if par.pp <= 1:
            return None
        from repro.parallel.plan import ParallelPlan, resolve_plan

        return resolve_plan(
            ParallelPlan(
                dp=par.dp, tp=par.tp, pp=par.pp,
                n_micro=par.n_micro, n_chunks=par.n_chunks,
                schedule=par.schedule, wave=par.wave,
                fbd_backward=par.fbd_backward,
            ),
            memory_cap_gib=self.run_cfg.dpp.memory_cap_gib,
        )

    def train(self):
        """The training workload: returns ``(state, history)``."""
        from repro.data.pipeline import DataConfig
        from repro.parallel.sharding import axis_rules
        from repro.train.loop import LoopConfig, train
        from repro.train.optim import OptimizerConfig

        rc, t = self.run_cfg, self.run_cfg.train
        cfg = self.model_cfg
        if cfg is None:
            raise ValueError("train workload needs an --arch")
        seq, batch, schedule = self._train_derived()
        data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                          global_batch=batch)
        ocfg = OptimizerConfig(
            lr=t.lr, schedule=schedule,
            warmup_steps=t.warmup_steps or max(t.steps // 10, 5),
            total_steps=t.steps,
        )
        loop = LoopConfig(
            n_steps=t.steps,
            log_every=t.log_every or max(t.steps // 10, 1),
            ckpt_dir=t.ckpt_dir or None,
            ckpt_every=t.ckpt_every,
            grad_accum=t.grad_accum,
            seed=rc.seed,
        )
        plan = self.parallel_plan()
        if plan is not None:
            from repro.launch.mesh import make_pipeline_mesh
            from repro.parallel.plan import plan_summary

            # per-axis divisibility: the batch first splits into grad_accum
            # macrobatches, each macrobatch into n_micro microbatches, and
            # the microbatch axis shards across dp groups (n_micro % dp is
            # plan.validate()'s job)
            ga = max(1, loop.grad_accum)
            if batch % ga != 0:
                raise ValueError(
                    f"global batch {batch} not divisible by "
                    f"train.grad_accum={ga}"
                )
            if (batch // ga) % plan.n_micro != 0:
                raise ValueError(
                    f"per-accumulation batch {batch // ga} (global {batch} "
                    f"/ grad_accum {ga}) not divisible by "
                    f"parallel.n_micro={plan.n_micro}"
                )
            mesh = make_pipeline_mesh(plan.pp, plan.dp, plan.tp)
            self.results["parallel"] = {
                **plan_summary(plan), "mesh": dict(mesh.shape),
            }
        else:
            mesh = self.mesh()
        log.info("arch=%s mesh=%s tokens/step=%d",
                 cfg.name, dict(mesh.shape), batch * seq)
        with mesh, axis_rules(mesh, self.sharding_rules("train")):
            state, history = train(
                cfg, ocfg, data, loop,
                collector=self.collector, tracer=self.tracer,
                hooks=self.step_hooks(), plan=plan,
                registry=self.metrics_registry,
                obs=self._rank_event_spec(plan),
                controller=self.ft_controller,
                compile_cache=self.compile_cache,
            )
        self.results["history"] = history
        return state, history

    # -------------------------------------------------------------- serve
    def serve(self):
        """The serving workload: returns ``(outputs, metrics)``.

        ``serve.continuous`` drives MegaServe (paged KV cache, scheduler,
        optional speculation); otherwise the static lockstep baseline runs.
        """
        cfg = self.model_cfg
        if cfg is None:
            raise ValueError("serve workload needs an --arch")
        s = self.run_cfg.serve
        if s.continuous:
            if cfg.input_kind != "tokens":
                raise ValueError(
                    f"{cfg.name}: continuous serving needs token archs"
                )
            if s.temperature != 0.0:
                raise ValueError(
                    "continuous serving decodes greedily "
                    "(preemption-by-recompute needs deterministic decode)"
                )
            return self._serve_continuous()
        if cfg.input_kind != "tokens" and cfg.family != "encdec":
            raise ValueError(
                f"{cfg.name} needs a modality frontend; serve token archs"
            )
        return self._serve_static()

    def _serve_continuous(self):
        from dataclasses import replace

        import jax

        from repro.models import get_model
        from repro.serve import MegaServe, Router, RouterConfig, get_drafter
        from repro.serve.server import make_poisson_workload

        cfg, rc, s = self.model_cfg, self.run_cfg, self.run_cfg.serve
        r = rc.router
        # always construct the RouterConfig so router.* validation fires
        # (bad policy / replica split fails loudly even on single-engine runs)
        router_cfg = RouterConfig(
            replicas=r.replicas, policy=r.policy,
            prefill_replicas=r.prefill_replicas,
            slo_ttft_s=r.slo_ttft_s, shed=r.shed,
        )
        use_router = (
            router_cfg.replicas > 1
            or router_cfg.disaggregated
            or router_cfg.slo_ttft_s > 0
            or router_cfg.policy != "round_robin"
        )
        m = get_model(cfg)
        params = m.init(cfg, jax.random.PRNGKey(0))
        specs, prompts, serve_cfg = make_poisson_workload(
            cfg, n=s.requests, rate=s.rate, prompt_lens=tuple(s.prompt_lens),
            max_new_range=(max(1, s.max_new // 4), s.max_new),
            num_slots=s.slots, block_size=s.block_size,
            num_blocks=s.num_blocks, seed=rc.seed, traffic=s.traffic,
        )
        serve_cfg = replace(
            serve_cfg, decode_path=s.decode_path,
            prefill_path=s.prefill_path,
            spec_decode=s.spec_decode, spec_k=s.spec_k,
            chunked_prefill=s.chunked_prefill, chunk_len=s.chunk_len,
        )
        drafter = None
        if s.spec_decode and s.drafter != "ngram":
            drafter = get_drafter(s.drafter, vocab_size=cfg.vocab_size,
                                  seed=rc.seed)
        if use_router:
            srv = Router.from_session(
                self, params, serve_cfg, router_cfg, drafter=drafter)
            replica_streams = [rep.streams for rep in srv.replicas]
        else:
            srv = MegaServe.from_session(
                self, params, serve_cfg, drafter=drafter)
            replica_streams = [srv.streams]
        if self.compile_cache is not None:
            # warm the full bucket ladder up front: with a populated on-disk
            # cache this deserializes executables instead of compiling, so
            # restart-to-first-token is dominated by weights, not XLA
            self.results["precompile"] = srv.precompile()
        for spec in specs:
            srv.submit(prompts[spec.rid], spec.max_new, arrival=spec.arrival)
        outs = srv.drain(on_step=self.notify_step)
        metrics = srv.metrics()
        if use_router:
            # replica lanes trace on their own rank=i tracers; fold them into
            # the session tracer so the shared trace_out export sees them
            self.tracer.events.extend(srv.trace_events())
        self.results["serve_config"] = {
            "num_slots": serve_cfg.num_slots,
            "block_size": serve_cfg.block_size,
            "num_blocks": serve_cfg.num_blocks,
            "replicas": router_cfg.replicas if use_router else 1,
            "policy": router_cfg.policy if use_router else "",
            "traffic": s.traffic,
        }
        # MegaServe attaches probe captures per generated token (StreamItem),
        # not per tick — replay them through on_step so capture-observing
        # plugins (MegaScope) see serving captures like training ones
        from repro.models.hooks import NULL_COLLECTOR

        if self.collector is not NULL_COLLECTOR:
            for streams in replica_streams:
                for items in streams.values():
                    for it in items:
                        if it.captures:
                            self.notify_step([], {"captures": it.captures})
        self.results["serve_metrics"] = metrics
        self.results["decode_path"] = (
            srv.replicas[0].decode_path if use_router else srv.decode_path
        )
        return outs, metrics

    def _serve_static(self):
        import jax
        import jax.numpy as jnp

        from repro.models import get_model
        from repro.parallel.sharding import axis_rules
        from repro.serve.engine import make_decode_step, make_prefill_step
        from repro.serve.sampler import sample

        cfg, s = self.model_cfg, self.run_cfg.serve
        m = get_model(cfg)
        mesh = self.mesh()
        with mesh, axis_rules(mesh, self.sharding_rules("decode")):
            params = m.init(cfg, jax.random.PRNGKey(0))
            B, P = s.batch, s.prompt_len
            cache_len = P + s.max_new
            cache = (m.init_cache(cfg, B, cache_len, P)
                     if cfg.family == "encdec"
                     else m.init_cache(cfg, B, cache_len))
            prompts = jax.random.randint(
                jax.random.PRNGKey(1), (B, P), 2, cfg.vocab_size)
            batch = {"tokens": prompts}
            if cfg.family == "encdec":
                batch["embeds"] = jax.random.normal(
                    jax.random.PRNGKey(2), (B, P, cfg.d_model), jnp.bfloat16)

            prefill = self.wrap_step(
                jax.jit(make_prefill_step(cfg, self.collector)))
            decode = self.wrap_step(
                jax.jit(make_decode_step(cfg, self.collector,
                                         temperature=s.temperature)))

            t0 = time.perf_counter()
            n_ev = len(self.tracer.events)
            with self.tracer.scope("prefill", kind="compute",
                                   tokens=B * P, batch=B):
                cache, logits = prefill(params, batch, cache)
                jax.block_until_ready(logits)
            t_prefill = time.perf_counter() - t0
            tok = sample(logits, temperature=s.temperature)
            self.notify_step(self.tracer.events[n_ev:], {})

            outs = [tok]
            t0 = time.perf_counter()
            for i in range(s.max_new - 1):
                n_ev = len(self.tracer.events)
                with self.tracer.scope("decode", kind="compute", step=i,
                                       active=B, tokens=B):
                    cache, logits, tok = decode(params, cache, tok,
                                                jnp.int32(P + i))
                outs.append(tok)
                self.notify_step(self.tracer.events[n_ev:], {})
            jax.block_until_ready(outs[-1])
            t_decode = time.perf_counter() - t0

        gen = jnp.stack(outs, axis=1)
        metrics = {
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "prefill_tok_s": B * P / max(t_prefill, 1e-9),
            "decode_tok_s": B * (s.max_new - 1) / max(t_decode, 1e-9),
        }
        if self.metrics_registry is not None:
            reg = self.metrics_registry
            reg.histogram("serve.prefill_s").observe(t_prefill)
            reg.counter("serve.tokens").inc(B * s.max_new)
            reg.gauge("serve.decode_tok_s").set(metrics["decode_tok_s"])
        self.results["serve_metrics"] = metrics
        return gen, metrics

    # -------------------------------------------------------------- trace
    def trace(self):
        """Offline MegaScan: simulate (or load) -> align -> detect.

        Returns the :class:`repro.core.tracing.detect.Diagnosis`; its
        summary (plus ground truth, when simulated) lands in
        ``results["diagnosis"]`` and the aligned events are exported via
        the shared ``trace_out`` / ``trace.out`` paths.
        """
        from repro.core.simkit.engine import FaultModel
        from repro.core.simkit.workload import ModelProfile, Topology
        from repro.core.tracing import (
            ClockModel,
            align_clocks,
            apply_alignment,
            detect,
            simulate_trace,
        )
        from repro.core.tracing.chrome import save_chrome
        from repro.core.tracing.tracer import load_jsonl, load_trace

        t = self.run_cfg.trace
        topo = Topology(dp=t.dp, pp=t.pp, tp=t.tp)
        truth = None
        if t.detect:
            # offline triage of a saved run: chrome JSON or streamed JSONL
            events = load_trace(t.detect)
        elif t.load:
            events = load_jsonl(t.load)
        else:
            faults = FaultModel(
                compute_slowdown={t.slow_rank: t.slow_factor},
                jitter=0.01, seed=self.run_cfg.seed,
            )
            events, truth = simulate_trace(
                topo, ModelProfile(), n_micro=t.n_micro, n_iters=t.n_iters,
                faults=faults, clocks=ClockModel(seed=self.run_cfg.seed),
            )
        sc = self.run_cfg.scan
        aligned = apply_alignment(events, align_clocks(events))
        diag = detect(
            aligned, topo,
            slow_ratio=sc.slow_ratio, candidate_frac=sc.candidate_frac,
            skew_margin=sc.skew_margin, late_frac=sc.late_frac,
            degrade_ratio=sc.degrade_ratio,
        )
        self.results["diagnosis"] = diag.summary()
        if truth is not None:
            self.results["truth"] = {
                "slow_ranks": truth["slow_ranks"],
                "detected": diag.slow_ranks == truth["slow_ranks"],
            }
        # aligned events flow through the session tracer so the shared
        # trace_out export (Session.finalize) sees them like any workload
        self.tracer.enabled = True
        self.tracer.events.extend(aligned)
        if t.out:
            out = Path(t.out)
            out.mkdir(parents=True, exist_ok=True)
            save_chrome(aligned, out / "trace.json")
            (out / "diagnosis.json").write_text(
                json.dumps(diag.summary(), indent=1))
            self.results["out"] = str(out)
        return diag

    # ------------------------------------------------------------- dryrun
    def dryrun(self):
        """Compile-analysis cells.  NOTE: ``repro.launch.dryrun`` must be
        imported (its XLA_FLAGS lines run) before jax initialises a backend
        — the CLI guarantees this ordering; direct Session users must
        import it first themselves."""
        from repro.launch.dryrun import run_cells

        d = self.run_cfg.dryrun
        result = run_cells(
            arch=self.run_cfg.arch or None,
            shape=d.shape or None,
            run_all=d.all,
            multi_pod=d.multi_pod,
            profile=d.profile or None,
            grad_accum=d.grad_accum,
            out=d.out,
            save_hlo=d.save_hlo,
            smoke=self.run_cfg.smoke,
            host_mesh=d.host_mesh,
        )
        self.results["dryrun"] = result
        return result
