"""End-to-end training driver: data pipeline + jitted train step + async
checkpointing + MegaScan tracing + optional MegaScope probes + supervised
fault tolerance.

The `python -m repro train` workload drives this loop through
``repro.app.Session`` (module plugins attach via :class:`StepHooks`); the
fault-tolerance tests call ``train`` directly.  The same loop drives the
multi-pod configuration (the jit step is mesh-agnostic — shardings come
from the installed axis rules).

With a :class:`repro.ft.FtController` attached (the ``ft`` module plugin),
the loop is *supervised*: any step failure — a chaos-injected crash, a
mitigation-requested exclusion restart, a guard rollback — restores the
latest checkpoint and resumes, bounded by ``ft.max_restarts`` with
exponential backoff.  Step-indexed batch determinism
(``SyntheticTokens.batch_at``) makes the replayed trajectory identical to a
fault-free run.  The controller's pending mitigation actions execute at
step boundaries:

* **compress_on** — rebuild the jit step with ``GradCompressor`` int8
  gradient sync + error feedback (degraded DP link mitigation);
* **replan_schedule** — re-resolve the MegaDPP wave schedule around a slow
  pipeline stage and rebuild the pipelined step;
* **exclude_restart** — mark the rank excluded (its induced slowdown
  stops, so the detector observes the recovery) and roll back through the
  elastic-restore path.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Any, Callable

import jax
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer, latest_step, restore
from repro.configs.base import ModelConfig
from repro.core.tracing.tracer import Tracer
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.models.hooks import NULL_COLLECTOR
from repro.train.optim import OptimizerConfig
from repro.train.train_step import (
    init_train_state,
    make_train_step,
    train_state_axes,
)

log = logging.getLogger("repro.train")


@dataclass
class LoopConfig:
    n_steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: str | None = None
    seed: int = 0
    grad_accum: int = 1


@dataclass
class StepHooks:
    """Plugin attach points threaded in by ``repro.app.Session``.

    ``wrap_step`` decorates the jitted step callable once, before the loop;
    ``on_step(events, metrics)`` observes each completed step — the MegaScan
    ``TraceEvent``s it appended and its (possibly device-resident) metrics.
    """

    wrap_step: Callable[[Callable], Callable] | None = None
    on_step: Callable[[list, dict], None] | None = None


class _MitigationRestart(RuntimeError):
    """The controller decided EXCLUDE_RESTART: roll back and resume."""


class _GuardRollback(RuntimeError):
    """An in-band guard tripped with guard_action=rollback."""


def _aot_train_step(jit_fn, avatars, *, cache, key_parts, registry):
    """AOT-compile the train step (``jit(...).lower().compile()``) through
    the persistent compile cache: a restarted process deserializes the prior
    run's executable instead of paying XLA at its first step.  Returns the
    compiled executable (a drop-in callable for the jitted step)."""
    from repro.core.compile_cache import aot_compile

    t0 = time.perf_counter()
    exe, hit = aot_compile(jit_fn, avatars, cache=cache, key_parts=key_parts)
    ms = (time.perf_counter() - t0) * 1e3
    log.info("train step AOT %s in %.0f ms",
             "cache hit" if hit else "compiled", ms)
    if registry is not None:
        registry.gauge("train.precompile_ms").set(ms)
    return exe


def _step_flops(jit_step, state, batch) -> float:
    """FLOPs XLA's cost analysis attributes to one jitted step (the
    model-flops/s numerator; recomputation under remat counts too, and a
    scanned layer body counts once).  ``Lowered.cost_analysis`` needs no
    compile where the backend offers it; the TPU offers it only on the
    compiled program (whose compile the persistent cache then serves to the
    first step).  A failure or a zero count is logged and returns 0.0,
    which leaves the flops/s series empty."""
    try:
        lowered = jit_step.lower(state, batch)
        cost = lowered.cost_analysis()
        if cost is None:
            cost = lowered.compile().cost_analysis()
        flops = float(cost["flops"])
    except Exception:
        log.exception("train step cost analysis failed; no flops/s series")
        return 0.0
    if flops == 0.0:
        log.warning("train step cost analysis counted no flops; no flops/s "
                    "series")
    return flops


def _place_state(cfg: ModelConfig, state):
    """Lay a fresh state out by the logical-axis rules of the installed mesh
    (ZeRO over ``data``, TP over ``model``), as every step after the first
    leaves it.  Left unplaced, the first step reads one replicated copy per
    device (a full-width pipelined step then overflows a chip), and the
    second step compiles again for the placed state its first one returned."""
    from repro.parallel.sharding import current_mesh_and_rules, param_shardings

    mesh, rules = current_mesh_and_rules()
    if mesh is None:
        return state
    return jax.device_put(
        state, param_shardings(train_state_axes(cfg), state, mesh, rules)
    )


def _shardings(state):
    """Per-leaf shardings of the live state (the elastic-restore target)."""
    return jax.tree.map(lambda x: getattr(x, "sharding", None), state)


def _route_links(plan, links) -> tuple[list, list]:
    """Split detected degraded links by the mesh axis they live on.

    Pure-DP runs (no plan) treat every link as a data link — the old
    behavior.  Composed plans map both endpoints through the plan topology
    (rank -> (dp, stage, tp) coordinates): links crossing the data axis are
    gradient-sync links (compressible), links crossing the stage axis are
    pipeline P2P links (replannable); anything else — tp links, diagonal
    pairs, out-of-range ranks — mitigates as neither.
    """
    links = [tuple(l) for l in (links or [])]
    if plan is None:
        return links, []
    from repro.parallel.plan import link_axis

    data = [l for l in links if link_axis(plan, l) == "data"]
    stage = [l for l in links if link_axis(plan, l) == "stage"]
    return data, stage


_MEM_STATS_SUPPORTED: bool | None = None  # probed once; CPU returns None


def _device_mem_bytes() -> float | None:
    """Live device memory (None on backends without allocator stats)."""
    global _MEM_STATS_SUPPORTED
    if _MEM_STATS_SUPPORTED is False:
        return None
    try:
        stats = jax.local_devices()[0].memory_stats()
        if stats and "bytes_in_use" in stats:
            _MEM_STATS_SUPPORTED = True
            return float(stats["bytes_in_use"])
    except Exception:
        pass
    _MEM_STATS_SUPPORTED = False
    return None


def _publish_step_metrics(registry, metrics, *, step_s, tokens, flops):
    """One step's standard series into the MetricsRegistry (host-side)."""
    registry.counter("train.steps").inc()
    registry.counter("train.tokens").inc(tokens)
    registry.histogram("train.step_time_s").observe(step_s)
    registry.gauge("train.tokens_per_s").set(tokens / max(step_s, 1e-9))
    if flops:
        registry.histogram("train.model_flops_per_s").observe(
            flops / max(step_s, 1e-9)
        )
    for k in ("loss", "grad_norm", "lr"):
        v = metrics.get(k)
        if v is not None and getattr(v, "ndim", 0) == 0:
            registry.gauge(f"train.{k}").set(float(v))
    mem = _device_mem_bytes()
    if mem is not None:
        registry.gauge(f"train.device_mem_bytes").set(mem)


def train(
    cfg: ModelConfig,
    ocfg: OptimizerConfig,
    data_cfg: DataConfig,
    loop: LoopConfig,
    *,
    collector=NULL_COLLECTOR,
    tracer: Tracer | None = None,
    state=None,
    hooks: StepHooks | None = None,
    plan=None,
    registry=None,
    obs=None,
    controller=None,
    compile_cache=None,
) -> tuple[Any, list[dict]]:
    # tracing defaults ON, matching MegaServe — the repo-wide documented
    # default (observability is always-on; pass a disabled Tracer to opt out)
    # ``registry`` (a repro.obs.MetricsRegistry) receives the standard train
    # series each step; ``obs`` (a repro.obs.RankEventSpec) synthesizes
    # per-rank events — and induces a live straggler when its slow_rank >= 0;
    # ``controller`` (a repro.ft.FtController) supervises the whole loop
    tracer = tracer or Tracer(rank=0, enabled=True)
    ds = SyntheticTokens(data_cfg)
    if state is None:
        with tracer.scope("init", op="init"):
            state = _place_state(
                cfg, init_train_state(cfg, jax.random.PRNGKey(loop.seed))
            )
    if controller is not None:
        controller.registry = registry

    # when compute dtype == param dtype the bf16 cast is a no-op and
    # state.params aliases state.master — donating the state would hand XLA
    # the same buffer twice (Execute() rejects it; under SPMD the surviving
    # devices then hang at the next collective).  Donation is a pure memory
    # optimization, so drop it for same-dtype (fp32 smoke) configs — and for
    # skip-guard runs, whose semantics need the pre-step buffers alive.
    may_donate = (
        np.dtype(cfg.compute_dtype) != np.dtype(cfg.param_dtype)
        and not (controller is not None
                 and controller.options.guard_action == "skip")
    )

    def build(plan_, compressor=None):
        """(Re)build the wrapped jit step — also the mitigation rebuild path
        (compression on, schedule replanned); runs under the ambient mesh
        Session installed around this loop."""
        raw = make_train_step(
            cfg, ocfg, grad_accum=loop.grad_accum, collector=collector,
            plan=plan_, compressor=compressor,
        )
        # pp>1 steps carry their static dispatch table; MegaScan folds it
        # into per-(microbatch, stage, F/B) events after each measured step
        pp = getattr(raw, "pipeline", None)
        donate = (
            ((0, 1) if compressor is not None else (0,)) if may_donate else ()
        )
        jit_fn = jax.jit(raw, donate_argnums=donate)
        inner = jit_fn
        if compile_cache is not None:
            # AOT warmup through the persistent cache — restricted to runs
            # whose sharding is trivial (no mesh, or a single-device mesh):
            # avatars carry no shardings, so a multi-device step compiled
            # from them would expect replicated inputs and reject the live
            # sharded state
            from repro.core.compile_cache import mesh_descriptor
            from repro.parallel.sharding import current_mesh_and_rules

            mesh = current_mesh_and_rules()[0]
            if (mesh is None or getattr(mesh, "empty", False)
                    or getattr(mesh, "size", 0) == 1):
                av = lambda t: jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t
                )
                avatars = [av(state)]
                if compressor is not None:
                    avatars.append(av(jax.eval_shape(
                        compressor.init, state.master
                    )))
                avatars.append(av(ds.batch_at(0)))
                inner = _aot_train_step(
                    jit_fn, tuple(avatars),
                    cache=compile_cache, registry=registry,
                    key_parts={
                        "model": cfg, "opt": ocfg, "data": data_cfg,
                        "grad_accum": loop.grad_accum, "plan": plan_,
                        "compress": compressor is not None,
                        "donate": list(donate),
                        "mesh": mesh_descriptor(mesh),
                        "state": [
                            f"{l.shape}/{l.dtype}"
                            for l in jax.tree.leaves(av(state))
                        ],
                    },
                )
        fn = inner
        if hooks is not None and hooks.wrap_step is not None:
            fn = hooks.wrap_step(inner)
        return fn, jit_fn, pp

    step_fn, jit_step, pp_info = build(plan)
    comp = None            # GradCompressor once the mitigation activates
    comp_err = None        # its error-feedback buffers
    comp_wire = (0, 0)     # (compressed, bf16-baseline) bytes per step

    start = 0
    ckpt = None
    if loop.ckpt_dir:
        ckpt = Checkpointer(loop.ckpt_dir)
        last = latest_step(loop.ckpt_dir)
        if last is not None:
            state, _ = restore(loop.ckpt_dir, state, shardings=_shardings(state))
            start = last
            log.info("restored checkpoint at step %d", start)
        elif controller is not None:
            # supervised runs always have a rollback target, even before
            # the first periodic save lands
            ckpt.save_async(state, 0, metadata={"arch": cfg.name})

    # MFU numerator, once: the flops XLA attributes to one step (lowering
    # uses the same in-memory jit, so the first real call still compiles
    # exactly once).  Only probed when someone will read the series.
    flops = 0.0
    if registry is not None:
        flops = _step_flops(jit_step, state, ds.batch_at(start))
        registry.gauge("train.step_flops").set(flops)
    tokens_per_step = data_cfg.global_batch * data_cfg.seq_len

    guards_on = controller is not None and (
        controller.options.guard_nan or controller.options.guard_spike > 0
    )
    skip_guard = (
        guards_on and controller.options.guard_action == "skip"
    )
    max_restarts = controller.options.max_restarts if controller is not None else 0
    backoff_s = controller.options.backoff_s if controller is not None else 0.0

    history: list[dict] = []
    t0 = time.perf_counter()
    step = start
    attempts = 0
    while step < loop.n_steps:
        try:
            if controller is not None:
                for act in controller.poll():
                    if act.kind == "exclude":
                        controller.excluded.update(act.slow_ranks)
                        controller.record(step, "mitigate:exclude", {
                            "ranks": sorted(act.slow_ranks),
                            "detect_step": act.detect_step,
                            "restart": ckpt is not None,
                        })
                        if ckpt is not None:
                            raise _MitigationRestart(
                                f"excluding ranks {sorted(act.slow_ranks)}"
                            )
                        log.warning("ft: excluding %s without restart "
                                    "(no ckpt_dir)", sorted(act.slow_ranks))
                    elif (data_stage := _route_links(plan, act.degraded_links))[0] \
                            and comp is None and (
                                plan is None or plan.pp <= 1 or plan.dp > 1
                    ):
                        # a data-axis link has a gradient sync to compress —
                        # either the pure DP/TP path, or a composed plan with
                        # dp>1 (the pipelined backward's data-axis all-reduce)
                        from repro.ft.compress import GradCompressor

                        data_links = data_stage[0]
                        comp = GradCompressor()
                        comp_err = comp.init(state.master)
                        comp_wire = comp.wire_bytes(state.master)
                        step_fn, jit_step, pp_info = build(plan, compressor=comp)
                        controller.replans += 1
                        controller.compression_on = True
                        controller.record(step, "mitigate:compress_on", {
                            "links": [list(l) for l in data_links],
                            "detect_step": act.detect_step,
                            "wire_bytes_per_sync": comp_wire[0],
                            "baseline_bytes_per_sync": comp_wire[1],
                        })
                        log.warning(
                            "ft: int8 gradient sync ON (%.2fx wire bytes) "
                            "for degraded links %s",
                            comp_wire[0] / max(comp_wire[1], 1),
                            [list(l) for l in data_links],
                        )
                    elif (act.slow_ranks or data_stage[1]) \
                            and plan is not None and plan.pp > 1:
                        # slow ranks or degraded stage-axis P2P links: route
                        # around them with a MegaDPP wave re-plan
                        from dataclasses import replace as _dc_replace
                        from types import SimpleNamespace

                        from repro.core.dpp.planner import Planner
                        from repro.core.simkit.workload import ModelProfile

                        planner = Planner(
                            plan.topology(),
                            ModelProfile(n_chunks=plan.n_chunks),
                            n_micro=plan.n_micro_local,
                        )
                        res = planner.replan(SimpleNamespace(
                            slow_ranks=list(act.slow_ranks),
                            degraded_links=data_stage[1],
                        ))
                        plan = _dc_replace(plan, schedule="wave", wave=res.wave)
                        step_fn, jit_step, pp_info = build(plan)
                        controller.replans += 1
                        controller.record(step, "mitigate:replan_schedule", {
                            "slow_ranks": sorted(act.slow_ranks),
                            "detect_step": act.detect_step,
                            "wave": res.wave,
                            "makespan_ms": round(res.makespan * 1e3, 3),
                        })
                        log.warning("ft: replanned pipeline schedule -> "
                                    "wave=%d around slow ranks %s",
                                    res.wave, sorted(act.slow_ranks))
                    else:
                        controller.record(step, "mitigate:replan_noop", {
                            "slow_ranks": sorted(act.slow_ranks),
                            "detect_step": act.detect_step,
                        })
                if controller.crash_due(step):
                    from repro.ft.chaos import InjectedCrash

                    raise InjectedCrash(f"chaos: injected crash at step {step}")

            batch = ds.batch_at(step)
            eff_obs = (
                controller.effective_obs(obs, step)
                if controller is not None else obs
            )
            if controller is not None:
                batch = controller.poison_batch(batch, step)
            # skip-guard runs keep the pre-step buffers alive (they never
            # donate) so a tripped guard can discard the poisoned update
            prev_state, prev_err = (state, comp_err) if skip_guard else (None, None)
            n_ev = len(tracer.events)
            t_step = time.perf_counter()
            with tracer.scope("train_step", op="train_step", mb=step):
                if comp is None:
                    state, metrics = step_fn(state, batch)
                else:
                    state, comp_err, metrics = step_fn(state, comp_err, batch)
                extra = 0.0
                if eff_obs is not None and eff_obs.slow_rank >= 0:
                    # induce the straggler INSIDE the scope: block until the
                    # real compute lands, then sleep the downclock excess —
                    # the step window genuinely stretches, like a slow rank's
                    jax.block_until_ready(metrics)
                    extra = eff_obs.extra_seconds(time.perf_counter() - t_step)
                    if extra > 0:
                        time.sleep(extra)
            step_s = time.perf_counter() - t_step
            if guards_on:
                verdict = controller.check_guards(
                    step,
                    float(metrics.get("loss", 0.0)),
                    float(metrics.get("grad_norm", 0.0)),
                )
                if verdict == "rollback":
                    raise _GuardRollback(f"guard tripped at step {step}")
                if verdict == "skip":
                    # discard the poisoned update (pre-step buffers are
                    # alive: skip-guard runs never donate) and move on —
                    # cheaper than a rollback, at the cost of diverging
                    # from the fault-free trajectory by one skipped batch
                    state, comp_err = prev_state, prev_err
                    del tracer.events[n_ev:]
                    step += 1
                    continue
            anchor = tracer.events[-1] if tracer.enabled else None
            if pp_info is not None and anchor is not None:
                from repro.core.dpp.executor import emit_pipeline_events

                # the train_step scope just closed; fold its wall into
                # per-(microbatch, stage, F/B) pipeline events
                emit_pipeline_events(
                    tracer.events, pp_info.table,
                    ts=anchor.ts, wall=anchor.dur, step_idx=step,
                )
            if eff_obs is not None and anchor is not None:
                from repro.obs.inject import emit_rank_events

                emit_rank_events(
                    tracer.events, eff_obs,
                    ts=anchor.ts, wall=anchor.dur, extra=extra, step=step,
                )
            if registry is not None:
                _publish_step_metrics(
                    registry, metrics,
                    step_s=step_s, tokens=tokens_per_step, flops=flops,
                )
                if comp is not None:
                    registry.counter("ft.wire_bytes_compressed").inc(comp_wire[0])
                    registry.counter("ft.wire_bytes_baseline").inc(comp_wire[1])
            if hooks is not None and hooks.on_step is not None:
                hooks.on_step(tracer.events[n_ev:], metrics)
            if (step + 1) % loop.log_every == 0 or step == loop.n_steps - 1:
                m = {k: float(v) for k, v in metrics.items()
                     if hasattr(v, "ndim") and v.ndim == 0}
                m["step"] = step + 1
                m["wall_s"] = round(time.perf_counter() - t0, 2)
                history.append(m)
                log.info("step %d: loss=%.4f lr=%.2e", step + 1,
                         m.get("loss", float("nan")), m.get("lr", 0.0))
            step += 1
            if ckpt and step % loop.ckpt_every == 0:
                ckpt.save_async(state, step, metadata={"arch": cfg.name})
        except Exception as e:  # noqa: BLE001 — the supervised recovery path
            attempts += 1
            if controller is None or ckpt is None or attempts > max_restarts:
                raise
            log.warning("step %d failed (%s: %s); recovery %d/%d",
                        step, type(e).__name__, e, attempts, max_restarts)
            # drain (not wait): a background save error here must not mask
            # the failure being recovered from — log and restore anyway
            bg = ckpt.drain()
            if bg is not None:
                log.warning("background checkpoint save failed (%s); "
                            "restoring from the previous one", bg)
            last = latest_step(loop.ckpt_dir)
            if last is None:
                raise
            if backoff_s > 0:
                time.sleep(min(backoff_s * 2 ** (attempts - 1), 30.0))
            # restore into the live state's exact shardings — a bare
            # device_put would land replicated, and the changed reduction
            # orders drift the replayed trajectory off the fault-free one
            state, _ = restore(loop.ckpt_dir, state, shardings=_shardings(state))
            if comp is not None:
                # error-feedback buffers are step-local state, not part of
                # the checkpoint contract: restart them at zero
                comp_err = comp.init(state.master)
            # drop history rows past the restored step — the replayed steps
            # re-append them; keeping both double-counts
            history[:] = [h for h in history if h["step"] <= last]
            if isinstance(e, _GuardRollback):
                controller.record_rollback(step, last)
            else:
                reason = ("exclude" if isinstance(e, _MitigationRestart)
                          else type(e).__name__)
                controller.record_restart(step, last, reason)
            log.info("restored checkpoint at step %d; resuming", last)
            step = last
    if ckpt:
        ckpt.wait()
    return state, history
