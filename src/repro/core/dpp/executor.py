"""JAX pipeline executor driven by MegaDPP schedule tables.

TPU-native realization of the paper's async-P2P runtime (DESIGN.md §2.2): the
planner picks the traversal order ahead-of-time; this executor lowers it into
a static sequence of per-stage compute + ring ``ppermute`` steps under
``shard_map``.  The backward pipeline falls out of autodiff (transpose of
ppermute is the reverse ppermute), with the forward traversal order — the
paper's contribution — fully schedule-controlled.

Interleaving layout: global block (c, s) = chunk c on stage s; value flow
(c, s) -> (c, s+1), wrapping (c, S-1) -> (c+1, 0), so every transfer is the
same +1 ring permute.

``params`` may be any pytree whose leaves are stage-major stacked
``[S, C, ...]`` arrays (a single array still works), and activations may have
any trailing shape — this is what lets the *real* transformer train step run
through the pipeline (``repro.models.pipeline`` builds the stacked block
pytrees and the per-cell ``block_fn``; ``repro.train.train_step`` drives it).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.dpp.schedule import Step
from repro.core.tracing.events import TraceEvent


@dataclass
class TimeTable:
    """Static dispatch tables [T, S]: what each stage runs/receives per step."""
    run_m: jnp.ndarray
    run_c: jnp.ndarray
    run_act: jnp.ndarray
    recv_m: jnp.ndarray
    recv_c: jnp.ndarray     # destination chunk slot at the receiver
    recv_act: jnp.ndarray
    recv_fin: jnp.ndarray   # receipt is a final output (write to out buffer)
    steps: int


def build_time_table(
    order: list[Step], n_stages: int, n_chunks: int, n_micro: int
) -> TimeTable:
    """Greedy legal placement of the desired visit order: at each step every
    stage runs its highest-priority *ready* pending (m, c) — the static
    analogue of "always pick the highest-priority ready input"."""
    fwd = [(m, c) for kind, m, c in order if kind == "F"]
    pending = {s: list(fwd) for s in range(n_stages)}
    ready: dict[tuple[int, int, int], int] = {
        (m, 0, 0): 0 for m in range(n_micro)
    }
    placed: list[list[tuple[int, int] | None]] = []
    done = 0
    total = n_stages * len(fwd)
    t = 0
    max_steps = total + n_stages * n_chunks * n_micro + 16
    while done < total and t < max_steps:
        row: list[tuple[int, int] | None] = []
        for s in range(n_stages):
            pick = None
            for i, (m, c) in enumerate(pending[s]):
                r = ready.get((m, c, s))
                if r is not None and r <= t:
                    pick = (i, m, c)
                    break
            if pick is None:
                row.append(None)
                continue
            i, m, c = pick
            pending[s].pop(i)
            done += 1
            row.append((m, c))
            # successor becomes ready next step
            if s < n_stages - 1:
                ready[(m, c, s + 1)] = t + 1
            elif c < n_chunks - 1:
                ready[(m, c + 1, 0)] = t + 1
        placed.append(row)
        t += 1
    if done < total:
        raise RuntimeError("schedule could not be legalized (cyclic order)")

    T = len(placed) + 1  # one extra step to flush the last permute
    S = n_stages
    run_m = jnp.zeros((T, S), jnp.int32)
    run_c = jnp.zeros((T, S), jnp.int32)
    run_act = jnp.zeros((T, S), bool)
    recv_m = jnp.zeros((T, S), jnp.int32)
    recv_c = jnp.zeros((T, S), jnp.int32)
    recv_act = jnp.zeros((T, S), bool)
    recv_fin = jnp.zeros((T, S), bool)
    for t, row in enumerate(placed):
        for s, entry in enumerate(row):
            if entry is None:
                continue
            m, c = entry
            run_m = run_m.at[t, s].set(m)
            run_c = run_c.at[t, s].set(c)
            run_act = run_act.at[t, s].set(True)
            # the receiver sees this value at step t+1
            dst = (s + 1) % S
            if s < S - 1:
                dc, fin = c, False
            elif c < n_chunks - 1:
                dc, fin = c + 1, False
            else:
                dc, fin = 0, True
            recv_m = recv_m.at[t + 1, dst].set(m)
            recv_c = recv_c.at[t + 1, dst].set(dc)
            recv_act = recv_act.at[t + 1, dst].set(True)
            recv_fin = recv_fin.at[t + 1, dst].set(fin)
    return TimeTable(run_m, run_c, run_act, recv_m, recv_c, recv_act, recv_fin, T)


def bubble_fraction(table: TimeTable) -> float:
    """Fraction of (step, stage) slots in the forward table that are idle.

    The denominator includes the final flush step, so the number is directly
    comparable across schedules for the same (S, C, n_micro) problem.
    """
    run_act = np.asarray(table.run_act)
    T, S = run_act.shape
    busy = int(run_act.sum())
    return 1.0 - busy / float(T * S)


def pipeline_apply(
    params: Any,                       # pytree of [S, C, ...] stacked blocks
    x_micro: jax.Array,                # [n_micro, ...] microbatch inputs
    table: TimeTable,
    *,
    mesh: jax.sharding.Mesh,
    axis: str = "stage",
    block_fn: Callable[[Any, jax.Array], jax.Array],
    data_axis: str | None = None,
    param_specs: Any | None = None,
) -> jax.Array:
    """Runs the pipelined forward; returns [n_micro, ...] final activations
    (replicated).  Differentiable — backward pipelines automatically.

    ``params`` leaves are split over the ``axis`` mesh dimension (stage-major
    leading axis); every other mesh axis sees them replicated unless
    ``param_specs`` (a matching pytree of ``PartitionSpec``, each starting
    with ``axis``) additionally slices weight dims over e.g. the tensor
    axis — the per-leaf tp sharding of ``models.pipeline``.  ``block_fn``
    receives one cell's params (leaves indexed down to ``[...]``, the chunk
    axis consumed) and one microbatch activation of shape ``x_micro.shape[1:]``.

    ``data_axis`` composes data parallelism: the leading microbatch axis of
    ``x_micro`` shards across that mesh axis, each dp group pipelines its
    local slice (``table`` must then be built for the *local* microbatch
    count), and the output keeps the same sharding.  The backward pass
    all-reduces parameter cotangents over the data axis for free: everything
    runs manual under ``shard_map``, and the transpose of a replicated-input
    broadcast is a psum over the mesh axes its spec does not mention.
    """
    S = mesh.shape[axis]
    rest = x_micro.shape[1:]
    n_local = x_micro.shape[0]
    if data_axis is not None:
        dp = mesh.shape[data_axis]
        if n_local % dp != 0:
            raise ValueError(
                f"n_micro={n_local} not divisible by mesh axis "
                f"{data_axis!r} of size {dp}"
            )
        n_local //= dp
    n_micro = n_local
    C = jax.tree.leaves(params)[0].shape[1]

    def body(params_loc, x_loc):
        # params_loc leaves [1, C, ...] (this stage's chunks); x_loc holds
        # this dp group's microbatches (all of them when data_axis is None)
        params_loc = jax.tree.map(lambda a: a[0], params_loc)
        sid = jax.lax.axis_index(axis)

        inbox0 = jnp.zeros((n_micro, C, *rest), x_loc.dtype)
        out0 = jnp.zeros((n_micro, *rest), x_loc.dtype)
        recv0 = jnp.zeros(rest, x_loc.dtype)

        def step(carry, t):
            inbox, out, recv = carry
            # 1. deposit what arrived on the wire last step
            r_act = table.recv_act[t, sid]
            r_fin = table.recv_fin[t, sid]
            r_m = table.recv_m[t, sid]
            r_c = table.recv_c[t, sid]
            dep = jnp.where(r_act & ~r_fin, recv, inbox[r_m, r_c])
            inbox = inbox.at[r_m, r_c].set(dep)
            fin = jnp.where(r_act & r_fin, recv, out[r_m])
            out = out.at[r_m].set(fin)
            # 2. run this stage's scheduled task
            act = table.run_act[t, sid]
            m = table.run_m[t, sid]
            c = table.run_c[t, sid]
            first = (c == 0) & (sid == 0)
            x_in = jnp.where(first, x_loc[m], inbox[m, c])
            p_c = jax.tree.map(lambda a: a[c], params_loc)
            y = block_fn(p_c, x_in)
            y = jnp.where(act, y, jnp.zeros_like(y))
            # 3. ship downstream
            recv_next = jax.lax.ppermute(
                y, axis, perm=[(i, (i + 1) % S) for i in range(S)]
            )
            return (inbox, out, recv_next), None

        (inbox, out, _), _ = jax.lax.scan(
            step, (inbox0, out0, recv0), jnp.arange(table.steps)
        )
        # outputs accumulate on stage 0 only; replicate across stages
        out = jnp.where(sid == 0, out, jnp.zeros_like(out))
        return jax.lax.psum(out, axis)

    x_spec = P() if data_axis is None else P(data_axis)
    if param_specs is None:
        param_specs = P(axis)  # broadcast: every leaf stage-sharded only
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(params, x_micro)


def reference_apply(params, x_micro, block_fn):
    """Sequential oracle: every block in (chunk, stage) order."""
    leaf = jax.tree.leaves(params)[0]
    S, C = leaf.shape[0], leaf.shape[1]

    def one(x):
        for c in range(C):
            for s in range(S):
                x = block_fn(jax.tree.map(lambda a: a[s, c], params), x)
        return x

    return jax.vmap(one)(x_micro)


def emit_pipeline_events(
    events: list[TraceEvent],
    table: TimeTable,
    *,
    ts: float,
    wall: float,
    bwd_cost: float = 2.0,
    step_idx: int = 0,
) -> None:
    """Synthesize per-(microbatch, stage, F/B) MegaScan events from the static
    dispatch table, scaled into a measured step's [ts, ts+wall] window.

    The forward traversal follows the table directly; the backward pipeline is
    autodiff's exact mirror (the transposed scan replays ticks in reverse), so
    its events are the reversed table stretched by ``bwd_cost``.  The chrome
    export then shows the schedule's *actual* bubble structure — one pid row
    per stage — without instrumenting the jitted scan body.
    """
    run_act = np.asarray(table.run_act)
    run_m = np.asarray(table.run_m)
    run_c = np.asarray(table.run_c)
    T, S = run_act.shape
    tick = max(wall, 1e-9) / (T * (1.0 + bwd_cost))
    fwd_span = T * tick
    for t in range(T):
        for s in range(S):
            if not run_act[t, s]:
                continue
            m, c = int(run_m[t, s]), int(run_c[t, s])
            args = {"mb": m, "chunk": c, "stage": s, "step": step_idx}
            events.append(TraceEvent(
                "pp_F", s, ts + t * tick, tick, "compute",
                {**args, "phase": "F"},
            ))
            events.append(TraceEvent(
                "pp_B", s,
                ts + fwd_span + (T - 1 - t) * bwd_cost * tick,
                bwd_cost * tick, "compute",
                {**args, "phase": "B"},
            ))
