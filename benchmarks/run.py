"""Benchmark harness — one function per paper table/claim (deliverable d).

Prints ``name,us_per_call,derived`` CSV rows.  Claims covered:

  §3 MegaScan  : near-zero tracing overhead; alignment accuracy; detection P/R
  §5 MegaDPP   : DFC/BFC memory + gradient-readiness trade (Fig. 3); async P2P
  §4 MegaFBD   : heterogeneous-cluster speedup; coordinator O(G) cost,
                 deadlock avoidance
  §6 MegaScope : capture overhead; compression ratios
  kernels      : reference-path timings (Pallas variants validated in tests)

    PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import numpy as np


def _timeit(fn, n=5, warmup=2) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6  # us


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")


# ------------------------------------------------------------- MegaScan ----


def bench_megascan_tracer_overhead() -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.tracing import Tracer

    x = jnp.ones((256, 256))
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    f(x).block_until_ready()
    base = _timeit(lambda: f(x).block_until_ready(), n=20)
    tr = Tracer(rank=0)

    def traced():
        with tr.scope("op", op="matmul"):
            f(x).block_until_ready()

    with_tr = _timeit(traced, n=20)
    ovh = (with_tr - base) / base * 100
    _row("megascan_tracer_overhead", with_tr, f"overhead_pct={ovh:.2f}")


def bench_megascan_alignment() -> None:
    from repro.core.simkit.workload import ModelProfile, Topology
    from repro.core.tracing import (
        ClockModel, align_clocks, apply_alignment, reconstruct_collectives,
        simulate_trace,
    )

    topo = Topology(dp=2, pp=2, tp=2)
    events, _ = simulate_trace(
        topo, ModelProfile(), n_micro=8, n_iters=2,
        clocks=ClockModel(offset_sigma=20e-3, drift_sigma=1e-4, seed=3),
    )
    t0 = time.perf_counter()
    aligned = apply_alignment(events, align_clocks(events))
    dt = (time.perf_counter() - t0) * 1e6

    def spread(evs):
        return float(np.median([
            max(i.ends.values()) - min(i.ends.values())
            for i in reconstruct_collectives(evs) if len(i.members) > 1
        ]))

    _row("megascan_clock_alignment", dt,
         f"median_skew_before_us={spread(events)*1e6:.1f};"
         f"after_us={spread(aligned)*1e6:.1f}")


def bench_megascan_detection() -> None:
    from repro.core.simkit.engine import FaultModel
    from repro.core.simkit.workload import ModelProfile, Topology
    from repro.core.tracing import (
        ClockModel, align_clocks, apply_alignment, detect, simulate_trace,
    )

    topo = Topology(dp=2, pp=2, tp=2)
    tp = fp = fn_ = 0
    t_us = []
    for seed in range(8):
        bad = seed % topo.world
        events, _ = simulate_trace(
            topo, ModelProfile(), n_micro=6, n_iters=2,
            faults=FaultModel(compute_slowdown={bad: 0.5}, jitter=0.01, seed=seed),
            clocks=ClockModel(seed=seed),
        )
        t0 = time.perf_counter()
        diag = detect(apply_alignment(events, align_clocks(events)), topo)
        t_us.append((time.perf_counter() - t0) * 1e6)
        tp += int(diag.slow_ranks == [bad])
        fp += len(set(diag.slow_ranks) - {bad})
        fn_ += int(bad not in diag.slow_ranks)
    prec = tp / max(tp + fp, 1)
    rec = tp / max(tp + fn_, 1)
    _row("megascan_detection", float(np.mean(t_us)),
         f"precision={prec:.2f};recall={rec:.2f};n=8")


# -------------------------------------------------------------- MegaDPP ----


def bench_dpp_schedules() -> None:
    from repro.core.dpp.planner import Planner
    from repro.core.simkit.workload import ModelProfile, Topology

    topo = Topology(dp=1, pp=4, tp=1)
    prof = ModelProfile(n_chunks=2, act_bytes=512 << 20)
    pl = Planner(topo, prof, n_micro=8, memory_cap=1 << 62)
    t0 = time.perf_counter()
    res = {w: pl._evaluate(w) for w in (1, 8)}
    dt = (time.perf_counter() - t0) * 1e6 / 2
    dfc, bfc = res[1], res[8]
    _row("dpp_dfc_vs_bfc", dt,
         f"dfc_peak_GiB={dfc[1]/2**30:.2f};bfc_peak_GiB={bfc[1]/2**30:.2f};"
         f"dfc_gradready_frac={dfc[2]/dfc[0]:.3f};"
         f"bfc_gradready_frac={bfc[2]/bfc[0]:.3f}")


def bench_dpp_zb_split() -> None:
    """ZB-inspired B/W split (paper §2.3.2 related work) vs plain 1F1B."""
    from repro.core.simkit.engine import Engine
    from repro.core.simkit.workload import ModelProfile, Topology, build_training_step

    topo = Topology(dp=1, pp=4, tp=1)
    prof = ModelProfile(fwd_time=1e-3, bwd_time=2e-3)
    t0 = time.perf_counter()
    mk_1f1b = Engine().run(build_training_step(topo, prof, n_micro=8)).makespan
    mk_zb = Engine().run(
        build_training_step(topo, prof, n_micro=8, schedule="zb")
    ).makespan
    dt = (time.perf_counter() - t0) * 1e6 / 2
    _row("dpp_zb_split", dt,
         f"1f1b_ms={mk_1f1b*1e3:.2f};zb_ms={mk_zb*1e3:.2f};"
         f"bubble_reduction={(1-mk_zb/mk_1f1b)*100:.1f}pct")


def bench_dpp_async_p2p() -> None:
    from repro.core.simkit.engine import Engine
    from repro.core.simkit.workload import ModelProfile, Topology, build_training_step

    topo = Topology(dp=1, pp=4, tp=1)
    prof = ModelProfile(p2p_bytes=64 << 20, fwd_time=5e-4, bwd_time=1e-3)

    def run(async_p2p, conc):
        order = build_training_step(topo, prof, n_micro=8, async_p2p=async_p2p)
        return Engine(link_concurrency=conc).run(order).makespan

    t0 = time.perf_counter()
    sync = run(False, 1)
    anc = run(True, 4)
    dt = (time.perf_counter() - t0) * 1e6 / 2
    _row("dpp_async_p2p", dt,
         f"sync_ms={sync*1e3:.2f};async_ms={anc*1e3:.2f};speedup={sync/anc:.2f}x")


def bench_dpp_executor() -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.dpp.executor import build_time_table, pipeline_apply
    from repro.core.dpp.schedule import sched_wave
    from repro.launch.mesh import auto_mesh

    S, C, n_micro, B, D = 4, 2, 8, 4, 64
    params = jax.random.normal(jax.random.PRNGKey(0), (S, C, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, B, D))
    mesh = auto_mesh((S,), ("stage",))
    table = build_time_table(sched_wave(n_micro, C, 2), S, C, n_micro)
    fn = jax.jit(lambda p, xx: pipeline_apply(
        p, xx, table, mesh=mesh, block_fn=lambda w, h: jnp.tanh(h @ w)))
    fn(params, x).block_until_ready()
    us = _timeit(lambda: fn(params, x).block_until_ready(), n=10)
    _row("dpp_pipeline_executor", us, f"stages={S};chunks={C};micro={n_micro}")


# -------------------------------------------------------------- MegaFBD ----


def bench_fbd_placement() -> None:
    from repro.core.fbd.ranks import (
        colocated_placement, evaluate_placement, plan_placement,
    )

    rows = []
    for frac_slow, slow in ((0.5, 0.4), (0.25, 0.6), (0.0, 1.0)):
        n = 8
        n_slow = int(n * frac_slow)
        speed = {d: 1.0 for d in range(n - n_slow)}
        speed |= {d: slow for d in range(n - n_slow, n)}
        t0 = time.perf_counter()
        dec = evaluate_placement(plan_placement(n, speed))
        col = evaluate_placement(colocated_placement(n, speed))
        dt = (time.perf_counter() - t0) * 1e6
        rows.append((frac_slow, col / dec))
    _row("fbd_heterogeneous_speedup", dt,
         ";".join(f"slowfrac{f}={s:.2f}x" for f, s in rows))


def bench_fbd_coordinator() -> None:
    from repro.core.fbd.coordinator import (
        BitVectorCoordinator, ThreadProgram, run_fcfs, run_with_coordinator,
    )

    # O(G) state scaling
    sizes = {}
    for g in (8, 64, 512):
        sizes[g] = BitVectorCoordinator({i: (0, 1) for i in range(g)}, 2, 1).state_bytes
    # deadlock rates on the cross-control scenario
    groups = {1: (0, 2), 2: (1, 3)}
    programs = [ThreadProgram(0, 0, [1]), ThreadProgram(1, 0, [2]),
                ThreadProgram(2, 1, [1]), ThreadProgram(3, 1, [2])]
    dead = sum(run_fcfs(programs, groups, 2, arrival_seed=s) is None
               for s in range(32))
    t0 = time.perf_counter()
    for _ in range(20):
        run_with_coordinator(programs, groups, 2)
    us = (time.perf_counter() - t0) * 1e6 / 20
    _row("fbd_coordinator", us,
         f"state_bytes_8_64_512={sizes[8]}/{sizes[64]}/{sizes[512]};"
         f"fcfs_deadlock_rate={dead}/32;coordinator_deadlocks=0/32")


# ------------------------------------------------------------- MegaScope ---


def bench_scope_capture_overhead() -> None:
    import jax

    from repro.configs import get_config
    from repro.core.scope import ProbeSpec, ScopeCollector
    from repro.models import get_model, make_batch
    from repro.models import lm as lm_mod

    cfg = get_config("qwen2-0.5b", smoke=True)
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 64, jax.random.PRNGKey(1))
    f_off = jax.jit(lambda p, b: lm_mod.loss_fn(cfg, p, b)[0])
    scope = ScopeCollector(probes=[ProbeSpec("mlp_hidden", "stats"),
                                   ProbeSpec("att_resid", "stats")])
    f_on = jax.jit(lambda p, b: lm_mod.loss_fn(cfg, p, b, scope)[1]["captures"])
    f_off(params, batch).block_until_ready()
    jax.block_until_ready(f_on(params, batch))
    off = _timeit(lambda: f_off(params, batch).block_until_ready(), n=10)
    on = _timeit(lambda: jax.block_until_ready(f_on(params, batch)), n=10)
    _row("scope_capture_overhead", on,
         f"baseline_us={off:.1f};overhead_pct={(on-off)/off*100:.2f}")


def bench_scope_compression() -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.scope.compress import histogram, stats_of, subsample

    x = jax.random.normal(jax.random.PRNGKey(0), (8, 512, 1024))
    full = x.size * 4
    t0 = time.perf_counter()
    s = stats_of(x)
    h = histogram(x)
    sub = subsample(x)
    jax.block_until_ready((s, h, sub))
    us = (time.perf_counter() - t0) * 1e6
    b_stats = sum(v.size * 4 for v in s.values())
    b_hist = h["hist"].size * 4 + h["edges"].size * 4
    b_sub = sub.size * 4
    _row("scope_compression", us,
         f"full_B={full};stats_B={b_stats}({full/b_stats:.0f}x);"
         f"hist_B={b_hist}({full/b_hist:.0f}x);sample_B={b_sub}({full/b_sub:.0f}x)")


# --------------------------------------------------------------- kernels ---


def bench_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.rglru.ref import rglru_ref
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    from repro.kernels.wkv6.ref import wkv6_ref

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8, 512, 1024), jnp.bfloat16)
    s = jnp.ones((1024,))
    f = jax.jit(lambda x: rmsnorm_ref(x, s))
    f(x).block_until_ready()
    us = _timeit(lambda: f(x).block_until_ready(), n=10)
    gbps = x.size * 2 * 2 / (us / 1e6) / 1e9
    _row("kernel_rmsnorm_ref", us, f"GBps={gbps:.1f};pallas=interpret-validated")

    B, S, H, K, D = 1, 512, 8, 2, 64
    q = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, K, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, K, D), jnp.bfloat16)
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, scale=D**-0.5, impl="xla"))
    fa(q, k, v).block_until_ready()
    us = _timeit(lambda: fa(q, k, v).block_until_ready(), n=5)
    fl = 4 * B * S * S * H * D
    _row("kernel_flash_attention_ref", us, f"GFLOPs={fl/(us/1e6)/1e9:.1f}")

    BH, T, Kd = 8, 256, 64
    r = jax.random.normal(key, (BH, T, Kd))
    w = jnp.exp(-jnp.exp(jax.random.normal(key, (BH, T, Kd))))
    u = jax.random.normal(key, (BH, Kd))
    fw = jax.jit(lambda r, w: wkv6_ref(r, r, r, w, u)[0])
    fw(r, w).block_until_ready()
    us = _timeit(lambda: fw(r, w).block_until_ready(), n=3)
    _row("kernel_wkv6_ref", us, f"tokens_per_s={BH*T/(us/1e6):.0f}")

    a = jax.random.uniform(key, (4, 512, 1024), minval=0.5, maxval=0.99)
    b = jax.random.normal(key, (4, 512, 1024))
    fr = jax.jit(lambda a, b: rglru_ref(a, b)[0])
    fr(a, b).block_until_ready()
    us = _timeit(lambda: fr(a, b).block_until_ready(), n=3)
    _row("kernel_rglru_ref", us, f"tokens_per_s={4*512/(us/1e6):.0f}")


# ------------------------------------------------------------------ main ---


def main() -> None:
    print("name,us_per_call,derived")
    bench_megascan_tracer_overhead()
    bench_megascan_alignment()
    bench_megascan_detection()
    bench_dpp_schedules()
    bench_dpp_zb_split()
    bench_dpp_async_p2p()
    bench_dpp_executor()
    bench_fbd_placement()
    bench_fbd_coordinator()
    bench_scope_capture_overhead()
    bench_scope_compression()
    bench_kernels()
    # roofline summary (per-table artifact analysis lives in roofline.py)
    try:
        import os as _os

        from benchmarks.roofline import load_all

        art_dir = next(
            (d for d in ("artifacts/dryrun_final", "artifacts/dryrun")
             if _os.path.isdir(d)), "artifacts/dryrun",
        )
        rows = load_all(art_dir)
        if rows:
            best = max(rows, key=lambda r: r["roofline_frac"])
            _row("roofline_cells", 0.0,
                 f"n_cells={len(rows)};best={best['arch']}/{best['shape']}"
                 f"@{best['mesh']}={best['roofline_frac']:.2f}")
    except Exception as e:  # noqa: BLE001
        _row("roofline_cells", 0.0, f"skipped({type(e).__name__})")


if __name__ == "__main__":
    main()
