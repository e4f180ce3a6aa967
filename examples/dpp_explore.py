"""MegaDPP exploration: DFC/BFC/wave trade-offs, best-effort planning under a
memory cap, telemetry-driven re-planning, and the real JAX pipeline executor.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/dpp_explore.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dpp.executor import build_time_table, pipeline_apply, reference_apply
from repro.core.dpp.planner import Planner
from repro.core.dpp.schedule import sched_wave
from repro.core.simkit.engine import FaultModel
from repro.core.simkit.workload import ModelProfile, Topology
from repro.core.tracing.detect import Diagnosis
from repro.launch.mesh import auto_mesh


def main() -> None:
    topo = Topology(dp=1, pp=4, tp=1)
    prof = ModelProfile(n_chunks=2, act_bytes=512 << 20, p2p_bytes=64 << 20)
    n_micro = 8

    print("== wave sweep (the DFC..BFC continuum) ==")
    print("wave  makespan_ms  peak_act_GiB  chunk0_grads_ready_ms")
    pl = Planner(topo, prof, n_micro=n_micro, memory_cap=1 << 62)
    for w in (1, 2, 4, 8):
        r = pl._evaluate(w)
        if r:
            mk, peak, gr = r
            print(f"{w:>4}  {mk*1e3:>10.2f}  {peak/2**30:>11.2f}  {gr*1e3:>18.2f}")

    print("\n== best-effort BFC under a 2 GiB activation cap ==")
    plan = Planner(topo, prof, n_micro=n_micro, memory_cap=2 << 30).plan()
    print(f"chosen: {plan.schedule_name} (wave={plan.wave}) "
          f"peak={plan.peak_memory/2**30:.2f} GiB makespan={plan.makespan*1e3:.2f} ms")

    print("\n== re-plan on MegaScan telemetry (stage 2 down-clocked) ==")
    pl2 = Planner(topo, prof, n_micro=n_micro, memory_cap=2 << 30)
    base = pl2.plan()
    new = pl2.replan(Diagnosis(slow_ranks=[2], candidate_ranks=[2], degraded_links=[]))
    print(f"healthy: wave={base.wave} makespan={base.makespan*1e3:.2f} ms | "
          f"degraded: wave={new.wave} makespan={new.makespan*1e3:.2f} ms")

    print("\n== JAX pipeline executor (4 stages x 2 chunks, 8 host devices) ==")
    S, C, B, D = 4, 2, 2, 16
    key = jax.random.PRNGKey(0)
    params = jax.random.normal(key, (S, C, D, D)) * 0.3
    x = jax.random.normal(jax.random.fold_in(key, 1), (n_micro, B, D))
    mesh = auto_mesh((S,), ("stage",))
    for wave, name in ((1, "DFC"), (n_micro, "BFC")):
        table = build_time_table(sched_wave(n_micro, C, wave), S, C, n_micro)
        out = pipeline_apply(params, x, table, mesh=mesh,
                             block_fn=lambda p, h: jnp.tanh(h @ p))
        ref = reference_apply(params, x, lambda p, h: jnp.tanh(h @ p))
        err = float(jnp.abs(out - ref).max())
        print(f"{name}: schedule steps={table.steps}, max |pipe - ref| = {err:.2e}")
        assert err < 1e-5


if __name__ == "__main__":
    main()
