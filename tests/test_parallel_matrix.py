"""Cross-axis parallelism parity matrix (PR-8 headline tests).

Systematic sweep of ``(dp, tp, pp) in {1,2}^3 x grad_accum in {1,2} x
schedule in {1f1b, wave}`` on the forced-host-device mesh: every *runnable*
cell must match the fused single-device train step (same grad_accum) to fp32
tolerance over a short loss trajectory, pipelined ga=1 cells additionally
gate on explicit per-leaf gradient parity, and every *must-refuse* cell must
assert its guard instead of silently replicating or miscomputing.

pp=1 cells run the fused step under a (data, model) host mesh — the sharded
DP/TP path — so the matrix covers both executors with one reference.
"""

import os

# host-device mesh (must be set before jax import)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core.dpp.executor import build_time_table
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.launch.mesh import auto_mesh, make_pipeline_mesh
from repro.models import lm
from repro.models import pipeline as pl
from repro.parallel.plan import ParallelPlan, forward_order, resolve_plan
from repro.parallel.sharding import DEFAULT_RULES, axis_rules
from repro.train.optim import OptimizerConfig
from repro.train.train_step import init_train_state, make_train_step

TINY = ModelConfig(
    name="pp-tiny", family="dense", num_layers=4, d_model=32, num_heads=4,
    num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=128, attn_kv_chunk=16,
    logits_chunk=16, vocab_pad_to=64,
    param_dtype="float32", compute_dtype="float32", remat="none",
)
OCFG = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=10)
BATCH, SEQ, N_STEPS = 8, 32, 2   # seq > attn_kv_chunk: chunked-flash path


def _dataset():
    return SyntheticTokens(DataConfig(
        vocab_size=TINY.vocab_size, seq_len=SEQ, global_batch=BATCH,
    ))


@functools.lru_cache(maxsize=None)
def _state0():
    return init_train_state(TINY, jax.random.PRNGKey(0))


def _run(step_fn, n_steps=N_STEPS):
    ds = _dataset()
    state = jax.tree.map(lambda x: x, _state0())
    losses = []
    for i in range(n_steps):
        state, m = step_fn(state, ds.batch_at(i))
        losses.append(float(m["loss"]))
    return losses


@functools.lru_cache(maxsize=None)
def _reference(ga: int):
    """Fused single-device trajectory at grad_accum=ga (computed once)."""
    return tuple(_run(jax.jit(make_train_step(TINY, OCFG, grad_accum=ga))))


def _cells():
    out = []
    for dp in (1, 2):
        for tp in (1, 2):
            for pp in (1, 2):
                for ga in (1, 2):
                    for sched in ("1f1b", "wave"):
                        if pp == 1 and sched != "1f1b":
                            continue  # schedule is a pipeline knob
                        out.append(pytest.param(
                            dp, tp, pp, ga, sched,
                            id=f"dp{dp}-tp{tp}-pp{pp}-ga{ga}-{sched}",
                        ))
    return out


@pytest.mark.parametrize("dp,tp,pp,ga,sched", _cells())
def test_matrix_cell_loss_parity(dp, tp, pp, ga, sched):
    if dp * tp * pp > len(jax.devices()):
        pytest.skip(f"needs {dp * tp * pp} devices")
    ref = _reference(ga)
    if pp == 1:
        if dp == tp == 1:
            # the reference itself; nothing to shard
            got = _run(jax.jit(make_train_step(TINY, OCFG, grad_accum=ga)))
        else:
            # sharded DP/TP path: fused step under a (data, model) mesh
            mesh = auto_mesh((dp, tp), ("data", "model"))
            with mesh, axis_rules(mesh, DEFAULT_RULES):
                got = _run(jax.jit(make_train_step(TINY, OCFG, grad_accum=ga)))
    else:
        plan = resolve_plan(ParallelPlan(
            dp=dp, tp=tp, pp=pp, n_micro=2 * dp, schedule=sched,
        ))
        mesh = make_pipeline_mesh(pp, dp, tp)
        with mesh, axis_rules(mesh, DEFAULT_RULES):
            step = jax.jit(make_train_step(
                TINY, OCFG, plan=plan, mesh=mesh, grad_accum=ga,
            ))
            got = _run(step)
    np.testing.assert_allclose(got, ref, rtol=2e-5)


@pytest.mark.parametrize("dp,tp", [(2, 1), (1, 2), (2, 2)])
def test_matrix_composed_grad_parity(dp, tp):
    """Explicit per-leaf gradient parity for composed pp=2 cells: the
    pipelined-sharded gradient must match the fused single-device gradient,
    leaf by leaf — dp cotangent psum, tp slice reassembly, and the ppermute
    transpose all checked in one gate."""
    pp = 2
    if dp * tp * pp > len(jax.devices()):
        pytest.skip(f"needs {dp * tp * pp} devices")
    plan = resolve_plan(ParallelPlan(dp=dp, tp=tp, pp=pp, n_micro=2 * dp))
    layout = pl.pipeline_layout(TINY, pp, plan.n_chunks, tp=tp)
    table = build_time_table(
        forward_order(plan), pp, plan.n_chunks, plan.n_micro_local,
    )
    mesh = make_pipeline_mesh(pp, dp, tp)
    params = lm.init(TINY, jax.random.PRNGKey(0))
    batch = _dataset().batch_at(0)

    g_ref = jax.grad(lambda p: lm.loss_fn(TINY, p, batch)[0])(params)
    with mesh, axis_rules(None):
        g_pp = jax.jit(jax.grad(lambda p: pl.pipeline_loss(
            TINY, p, batch, layout=layout, table=table, mesh=mesh,
            n_micro=plan.n_micro, dp=dp)[0]))(params)
    flat_ref, flat_pp = jax.tree.leaves(g_ref), jax.tree.leaves(g_pp)
    assert len(flat_ref) == len(flat_pp)
    for a, b in zip(flat_ref, flat_pp):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=5e-4, atol=1e-5,
        )


def test_seq64_pipeline_regression():
    """Regression: pp=2 at seq_len=64 (4 chunked-flash KV chunks) used to
    crash with a manual-axes tracing error because the flash custom_vjp's
    backward traces lazily during the gradient pull-back, *after* the
    forward's ``axis_rules(None)`` scope had exited.  The pipelined train
    step now keeps the whole grad computation inside that scope; this cell
    must match the fused step exactly."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    seq = 64
    ds = SyntheticTokens(DataConfig(
        vocab_size=TINY.vocab_size, seq_len=seq, global_batch=4,
    ))

    def losses(plan=None, mesh=None):
        state = init_train_state(TINY, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(TINY, OCFG, plan=plan, mesh=mesh))
        out = []
        for i in range(2):
            state, m = step(state, ds.batch_at(i))
            out.append(float(m["loss"]))
        return out

    ref = losses()
    plan = resolve_plan(ParallelPlan(pp=2, n_micro=2))
    got = losses(plan=plan, mesh=make_pipeline_mesh(2))
    np.testing.assert_allclose(got, ref, rtol=2e-5)


# ------------------------------------------------------- must-refuse cells --


def test_refuses_indivisible_micro_over_dp():
    with pytest.raises(ValueError, match="not divisible by dp"):
        resolve_plan(ParallelPlan(pp=2, dp=2, n_micro=3))


def test_refuses_mismatched_mesh_shape():
    plan = resolve_plan(ParallelPlan(pp=2, dp=2, n_micro=4))
    with pytest.raises(ValueError, match="mesh shaped"):
        make_train_step(TINY, OCFG, plan=plan, mesh=make_pipeline_mesh(2))


def test_refuses_tp_on_non_dense_family():
    rwkv = get_config("rwkv6-3b", smoke=True)
    with pytest.raises(ValueError, match="dense GQA"):
        pl.pipeline_layout(rwkv, pp=2, tp=2)


def test_refuses_tp_on_indivisible_widths():
    with pytest.raises(ValueError, match="divide"):
        pl.pipeline_layout(TINY.replace(num_kv_heads=1), pp=2, tp=2)


def test_refuses_layers_indivisible_by_cells():
    with pytest.raises(ValueError, match="not divisible"):
        pl.pipeline_layout(TINY.replace(num_layers=6), pp=2, n_chunks=2)


def test_refuses_batch_indivisible_by_micro():
    plan = resolve_plan(ParallelPlan(pp=2, n_micro=3))
    mesh = make_pipeline_mesh(2)
    layout = pl.pipeline_layout(TINY, 2, 1)
    table = build_time_table(forward_order(plan), 2, 1, plan.n_micro_local)
    batch = _dataset().batch_at(0)   # global batch 8, n_micro 3
    with pytest.raises(ValueError, match="not divisible by n_micro"):
        pl.pipeline_loss(TINY, lm.init(TINY, jax.random.PRNGKey(0)), batch,
                         layout=layout, table=table, mesh=mesh, n_micro=3)


def test_refuses_compressor_without_data_axis():
    from repro.ft.compress import GradCompressor

    plan = resolve_plan(ParallelPlan(pp=2))
    with pytest.raises(ValueError, match="no data axis"):
        make_train_step(TINY, OCFG, plan=plan, mesh=make_pipeline_mesh(2),
                        compressor=GradCompressor())
