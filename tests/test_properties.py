"""Hypothesis property tests on system invariants (deliverable c)."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare env: deterministic few-example fallback
    from _hypothesis_shim import given, settings
    import _hypothesis_shim as st

import jax

from repro.core.dpp.executor import build_time_table
from repro.core.dpp.schedule import sched_wave
from repro.core.simkit.engine import Engine
from repro.core.simkit.workload import ModelProfile, Topology, build_training_step
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.parallel.sharding import DEFAULT_RULES, logical_to_spec


# ----------------------------------------------------------- scheduling ----


@settings(max_examples=40, deadline=None)
@given(n_micro=st.integers(1, 12), n_chunks=st.integers(1, 4),
       wave=st.integers(1, 12))
def test_wave_schedule_is_complete_and_unique(n_micro, n_chunks, wave):
    steps = sched_wave(n_micro, n_chunks, wave)
    fwd = [(m, c) for k, m, c in steps if k == "F"]
    bwd = [(m, c) for k, m, c in steps if k == "B"]
    assert sorted(fwd) == sorted(bwd)
    assert len(set(fwd)) == n_micro * n_chunks == len(fwd)
    # B(m, c) never precedes F(m, c)
    seen = set()
    for k, m, c in steps:
        if k == "F":
            seen.add((m, c))
        else:
            assert (m, c) in seen


@settings(max_examples=20, deadline=None)
@given(n_micro=st.integers(1, 6), n_chunks=st.integers(1, 3),
       n_stages=st.integers(1, 4), wave=st.integers(1, 6))
def test_time_table_legalizes_any_wave(n_micro, n_chunks, n_stages, wave):
    table = build_time_table(
        sched_wave(n_micro, n_chunks, wave), n_stages, n_chunks, n_micro
    )
    # every stage runs every (m, c) exactly once
    run = np.asarray(table.run_act)
    m = np.asarray(table.run_m)
    c = np.asarray(table.run_c)
    for s in range(n_stages):
        done = {(int(m[t, s]), int(c[t, s])) for t in range(table.steps) if run[t, s]}
        assert len(done) == n_micro * n_chunks


@settings(max_examples=15, deadline=None)
@given(dp=st.integers(1, 2), pp=st.integers(1, 3), tp=st.integers(1, 2),
       n_micro=st.integers(1, 4))
def test_1f1b_workload_never_deadlocks(dp, pp, tp, n_micro):
    topo = Topology(dp=dp, pp=pp, tp=tp)
    order = build_training_step(topo, ModelProfile(), n_micro=n_micro)
    res = Engine().run(order)  # raises DeadlockError on schedule bugs
    assert res.makespan > 0
    # conservation: forward+backward compute tasks on every rank
    per_rank = res.by_rank()
    for r, recs in per_rank.items():
        n_comp = sum(1 for t in recs if t.kind == "compute")
        assert n_comp == 2 * n_micro * ModelProfile().n_chunks


# ------------------------------------------------------------- sharding ----


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_logical_spec_axes_never_collide_or_overdivide(data):
    from jax.sharding import AbstractMesh

    # abstract mesh: shape-only, no physical devices required
    mesh = AbstractMesh((2, 2, 2), ("pod", "data", "model"))
    names = list(DEFAULT_RULES)
    k = data.draw(st.integers(1, 4))
    axes = tuple(data.draw(st.sampled_from(names)) for _ in range(k))
    shape = tuple(data.draw(st.sampled_from([1, 2, 3, 4, 6, 8, 128])) for _ in range(k))
    spec = logical_to_spec(axes, shape, mesh, DEFAULT_RULES)
    used: list[str] = []
    for i, part in enumerate(spec):
        if part is None:
            continue
        parts = part if isinstance(part, tuple) else (part,)
        total = 1
        for ax in parts:
            assert ax not in used, "mesh axis used twice"
            used.append(ax)
            total *= mesh.shape[ax]
        assert shape[i] % total == 0, "sharding must divide the dim"


# ------------------------------------------------------------------ data ---


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), step=st.integers(0, 1000))
def test_data_determinism_property(seed, step):
    cfg = DataConfig(vocab_size=512, seq_len=32, global_batch=2, seed=seed)
    a = SyntheticTokens(cfg).batch_at(step)
    b = SyntheticTokens(cfg).batch_at(step)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 512
    # targets are tokens shifted by one position
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["targets"][:, :-1])


# ------------------------------------------------------------ compression --


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), scale=st.floats(1e-4, 1e3))
def test_grad_compression_relative_error_bound(seed, scale):
    from repro.ft.compress import GradCompressor

    import jax.numpy as jnp

    comp = GradCompressor(block=64, bits=8)
    g = jax.random.normal(jax.random.PRNGKey(seed), (512,)) * scale
    deq, _ = comp.apply({"g": g}, {"g": jnp.zeros_like(g)})
    num = float(jnp.linalg.norm(deq["g"] - g))
    den = float(jnp.linalg.norm(g)) + 1e-30
    assert num / den < 0.02


# ----------------------------------------------- composed-plan invariants --


@settings(max_examples=30, deadline=None)
@given(S=st.integers(1, 4), C=st.integers(1, 3), g=st.integers(1, 3))
def test_restack_params_is_a_permutation_roundtrip(S, C, g):
    """restack_params is a pure permutation of the stacked-group axis: cell
    (s, c) holds global groups (c*S + s)*g + j, and the inverse
    swapaxes/reshape recovers the canonical [G, ...] stacking exactly."""
    import jax.numpy as jnp

    from repro.models import pipeline as pl

    G = S * C * g
    layout = pl.PipelineLayout("seg0", ("dense",), G, S, C, g)
    leaf = jnp.arange(float(G * 2)).reshape(G, 2)
    tree = {"w": leaf, "b": leaf[:, :1] + 100.0}
    stacked = pl.restack_params(tree, layout)
    w = np.asarray(stacked["w"])
    assert w.shape == (S, C, g, 2)
    for s in range(S):
        for c in range(C):
            for j in range(g):
                np.testing.assert_array_equal(
                    w[s, c, j], np.asarray(leaf[(c * S + s) * g + j])
                )
    back = jnp.swapaxes(stacked["w"], 0, 1).reshape(G, 2)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(leaf))
    assert np.asarray(stacked["b"]).shape == (S, C, g, 1)


@settings(max_examples=40, deadline=None)
@given(dp=st.sampled_from([1, 2]), tp=st.sampled_from([1, 2]),
       pp=st.integers(1, 4), mult=st.integers(1, 3),
       n_chunks=st.sampled_from([1, 2]),
       schedule=st.sampled_from(["1f1b", "dfc", "bfc", "wave"]))
def test_forward_order_is_dp_local_and_complete(dp, tp, pp, mult, n_chunks, schedule):
    """Under any composed plan the forward order visits every *dp-local*
    (microbatch, chunk) pair exactly once — dp shards the microbatch axis,
    tp never changes the traversal."""
    from repro.parallel.plan import ParallelPlan, forward_order

    plan = ParallelPlan(
        dp=dp, tp=tp, pp=pp, n_micro=dp * mult, n_chunks=n_chunks,
        schedule=schedule, wave=max(1, mult // 2),
    ).validate()
    fwd = [(m, c) for k, m, c in forward_order(plan) if k == "F"]
    want = {(m, c) for m in range(mult) for c in range(n_chunks)}
    assert len(fwd) == len(want) and set(fwd) == want
    # tp is orthogonal to the traversal
    base = ParallelPlan(dp=dp, tp=1, pp=pp, n_micro=dp * mult,
                        n_chunks=n_chunks, schedule=schedule,
                        wave=max(1, mult // 2))
    assert forward_order(base) == forward_order(plan)


@settings(max_examples=25, deadline=None)
@given(dp=st.sampled_from([1, 2]), pp=st.integers(1, 3),
       mult=st.integers(1, 3), n_chunks=st.sampled_from([1, 2]),
       schedule=st.sampled_from(["1f1b", "dfc", "bfc", "wave"]))
def test_time_table_dispatch_and_dataflow_under_composed_plans(
    dp, pp, mult, n_chunks, schedule
):
    """The legalized table for a composed plan (a) dispatches every
    (microbatch, chunk) on every stage exactly once, and (b) never runs a
    consumer cell before its producer: stage s needs stage s-1's (m, c),
    and chunk c's entry stage needs the last stage's (m, c-1)."""
    from repro.parallel.plan import ParallelPlan, forward_order

    plan = ParallelPlan(
        dp=dp, pp=pp, n_micro=dp * mult, n_chunks=n_chunks,
        schedule=schedule, wave=max(1, mult // 2),
    ).validate()
    nm = plan.n_micro_local
    table = build_time_table(forward_order(plan), pp, n_chunks, nm)
    run = np.asarray(table.run_act)
    ms = np.asarray(table.run_m)
    cs = np.asarray(table.run_c)
    times: dict[tuple[int, int, int], int] = {}
    for t in range(table.steps):
        for s in range(pp):
            if run[t, s]:
                key = (int(ms[t, s]), int(cs[t, s]), s)
                assert key not in times, f"duplicate dispatch {key}"
                times[key] = t
    assert len(times) == pp * nm * n_chunks
    for (m, c, s), t in times.items():
        if s > 0:
            assert times[(m, c, s - 1)] < t
        elif c > 0:
            assert times[(m, c - 1, pp - 1)] < t
