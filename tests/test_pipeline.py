"""Real pipeline-parallel training: MegaDPP's executor on actual model
weights — params restacking, schedule-controlled train-step parity vs the
fused reference, ParallelPlan threading (Session/CLI), MegaFBD's decoupled
backward attach, MegaScan bubble events — plus the schedule/table/mesh
satellite guards."""

import os

# host-device mesh for the pipeline tests (must be set before jax import)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core.dpp.executor import (
    build_time_table,
    bubble_fraction,
    emit_pipeline_events,
)
from repro.core.dpp.schedule import sched_bfc, sched_dfc, sched_wave
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.launch.mesh import make_host_mesh, make_pipeline_mesh
from repro.models import lm
from repro.models import pipeline as pl
from repro.models.model import make_batch
from repro.parallel.plan import ParallelPlan, forward_order, resolve_plan
from repro.parallel.sharding import axis_rules
from repro.train.optim import OptimizerConfig
from repro.train.train_step import init_train_state, make_train_step

TINY = ModelConfig(
    name="pp-tiny", family="dense", num_layers=4, d_model=32, num_heads=4,
    num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=128, attn_kv_chunk=16,
    logits_chunk=16, vocab_pad_to=64,
    param_dtype="float32", compute_dtype="float32", remat="none",
)
OCFG = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=10)


def _train_losses(cfg, plan=None, mesh=None, n_steps=3, batch=4, seq=16):
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      global_batch=batch)
    ds = SyntheticTokens(data)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, OCFG, plan=plan, mesh=mesh))
    losses = []
    for i in range(n_steps):
        state, m = step(state, ds.batch_at(i))
        losses.append(float(m["loss"]))
    return losses, state


# ------------------------------------------------------------- restacking ---


def test_restack_params_is_chunk_major():
    layout = pl.pipeline_layout(TINY, pp=2, n_chunks=2)
    assert layout.groups_per_cell == 1
    seg = {"w": jnp.arange(4.0)[:, None] * jnp.ones((4, 3))}
    out = pl.restack_params(seg, layout)
    assert out["w"].shape == (2, 2, 1, 3)
    # cell (s, c) holds global group c*S + s (execution order of the ring)
    for s in range(2):
        for c in range(2):
            assert float(out["w"][s, c, 0, 0]) == c * 2 + s


def test_restack_groups_per_cell():
    cfg = TINY.replace(num_layers=8)
    layout = pl.pipeline_layout(cfg, pp=2, n_chunks=2)
    assert layout.groups_per_cell == 2
    seg = jnp.arange(8.0)
    out = pl.restack_params(seg, layout)
    # cell (s, c) covers consecutive groups [(c*S + s)*gpc, ...)
    assert out.tolist() == [[[0.0, 1.0], [4.0, 5.0]], [[2.0, 3.0], [6.0, 7.0]]]


def test_pipeline_layout_rejections():
    with pytest.raises(ValueError, match="not divisible"):
        pl.pipeline_layout(TINY.replace(num_layers=5), pp=2, n_chunks=2)
    moe = get_config("phi3.5-moe-42b-a6.6b", smoke=True)
    with pytest.raises(ValueError, match="MoE"):
        pl.pipeline_layout(moe, pp=2)
    mrope = get_config("qwen2-vl-7b", smoke=True)
    with pytest.raises(ValueError, match="mrope"):
        pl.pipeline_layout(mrope, pp=2)


# ------------------------------------------------- loss / forward parity ----


@pytest.mark.parametrize("family_cfg", [
    TINY,
    pytest.param(
        get_config("rwkv6-3b", smoke=True).replace(
            param_dtype="float32", compute_dtype="float32", remat="none"),
        id="rwkv"),
])
def test_pipeline_loss_matches_fused_forward(family_cfg):
    cfg = family_cfg
    pp = 2
    n_chunks = 2 if cfg.num_layers % 4 == 0 else 1
    layout = pl.pipeline_layout(cfg, pp, n_chunks)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, 4, 16, jax.random.PRNGKey(1))
    n_micro = 4
    table = build_time_table(
        sched_wave(n_micro, n_chunks, 2), pp, n_chunks, n_micro
    )
    mesh = make_pipeline_mesh(pp)

    loss_ref, _ = lm.loss_fn(cfg, params, batch)
    loss_pp, metrics = jax.jit(
        lambda p, b: pl.pipeline_loss(
            cfg, p, b, layout=layout, table=table, mesh=mesh, n_micro=n_micro)
    )(params, batch)
    np.testing.assert_allclose(
        float(loss_pp), float(loss_ref), rtol=2e-6, atol=1e-6
    )

    g_ref = jax.grad(lambda p: lm.loss_fn(cfg, p, batch)[0])(params)
    g_pp = jax.jit(jax.grad(lambda p: pl.pipeline_loss(
        cfg, p, batch, layout=layout, table=table, mesh=mesh,
        n_micro=n_micro)[0]))(params)
    flat_ref, flat_pp = jax.tree.leaves(g_ref), jax.tree.leaves(g_pp)
    assert len(flat_ref) == len(flat_pp)
    for a, b in zip(flat_ref, flat_pp):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=5e-4, atol=1e-5
        )


# --------------------------------------------------- train-step parity ------


def test_pp1_plan_is_bitwise_identical_to_plain_step():
    ref, ref_state = _train_losses(TINY)
    p1, p1_state = _train_losses(TINY, plan=ParallelPlan(pp=1, n_micro=1))
    assert p1 == ref
    for a, b in zip(jax.tree.leaves(p1_state.master),
                    jax.tree.leaves(ref_state.master)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("schedule", ["1f1b", "wave"])
def test_pp2_train_parity_three_steps(schedule):
    """Acceptance bar: pp=2 on the host mesh matches the reference loss to
    fp32 tolerance across >= 3 steps for 1f1b and wave."""
    ref, _ = _train_losses(TINY)
    plan = resolve_plan(ParallelPlan(
        pp=2, n_micro=4, n_chunks=2, schedule=schedule,
    ))
    pp, _ = _train_losses(TINY, plan=plan, mesh=make_pipeline_mesh(2))
    np.testing.assert_allclose(pp, ref, rtol=2e-5)


def test_fbd_backward_attach_matches():
    """MegaFBD's decoupled backward (vjp split) is numerically the fused
    grad: same 3-step loss trajectory through the pipelined step."""
    plan = resolve_plan(ParallelPlan(pp=2, n_micro=4, n_chunks=2))
    fused, _ = _train_losses(TINY, plan=plan, mesh=make_pipeline_mesh(2))
    dec, _ = _train_losses(
        TINY,
        plan=resolve_plan(ParallelPlan(
            pp=2, n_micro=4, n_chunks=2, fbd_backward=True)),
        mesh=make_pipeline_mesh(2),
    )
    np.testing.assert_allclose(dec, fused, rtol=1e-6)


def test_wave_zero_resolves_via_planner():
    plan = resolve_plan(ParallelPlan(pp=2, n_micro=8, n_chunks=2,
                                     schedule="wave", wave=0))
    assert 1 <= plan.wave <= 8
    # default n_micro fills in
    plan2 = resolve_plan(ParallelPlan(pp=4))
    assert plan2.n_micro == 8


def test_pipeline_step_needs_stage_mesh():
    plan = resolve_plan(ParallelPlan(pp=2, n_micro=2))
    with pytest.raises(ValueError, match="stage"):
        make_train_step(TINY, OCFG, plan=plan, mesh=None)


def test_pipeline_composition_guards():
    # composed axes are allowed now, but the microbatch axis must still
    # shard evenly across dp groups
    with pytest.raises(ValueError, match="not divisible by dp"):
        resolve_plan(ParallelPlan(pp=2, dp=2, n_micro=3))
    # a dp=2 plan resolves (and defaults n_micro to 2*pp*dp)
    plan = resolve_plan(ParallelPlan(pp=2, dp=2))
    assert plan.n_micro == 8 and plan.n_micro_local == 4
    # the composed step demands the matching per-axis mesh shape
    with pytest.raises(ValueError, match="mesh shaped"):
        make_train_step(TINY, OCFG, plan=plan, mesh=make_pipeline_mesh(2))
    # compression without a data axis still has nothing to compress
    from repro.ft.compress import GradCompressor

    with pytest.raises(ValueError, match="no data axis"):
        make_train_step(TINY, OCFG, plan=resolve_plan(ParallelPlan(pp=2)),
                        mesh=make_pipeline_mesh(2),
                        compressor=GradCompressor())
    # tp inside the pipeline is dense-GQA only, and widths must divide
    with pytest.raises(ValueError, match="dense GQA"):
        rwkv = get_config("rwkv6-3b", smoke=True)
        pl.pipeline_layout(rwkv, pp=2, tp=2)
    with pytest.raises(ValueError, match="divide"):
        pl.pipeline_layout(TINY.replace(num_kv_heads=1), pp=2, tp=2)


# ----------------------------------------------- MegaScan bubble events -----


def test_pipeline_emits_megascan_events():
    from repro.core.tracing.tracer import Tracer
    from repro.train.loop import LoopConfig, train

    plan = resolve_plan(ParallelPlan(pp=2, n_micro=2, n_chunks=2))
    mesh = make_pipeline_mesh(2)
    tracer = Tracer(rank=0, enabled=True)
    data = DataConfig(vocab_size=TINY.vocab_size, seq_len=16, global_batch=4)
    with mesh, axis_rules(mesh):
        train(TINY, OCFG, data, LoopConfig(n_steps=2, log_every=1),
              tracer=tracer, plan=plan)
    f_ev = [e for e in tracer.events if e.name == "pp_F"]
    b_ev = [e for e in tracer.events if e.name == "pp_B"]
    # every (microbatch, chunk) runs once per stage, per step
    assert len(f_ev) == 2 * plan.n_micro * plan.n_chunks * plan.pp
    assert len(b_ev) == len(f_ev)
    assert {e.rank for e in f_ev} == {0, 1}          # one chrome row per stage
    steps = [e for e in tracer.events if e.name == "train_step"]
    for e in f_ev + b_ev:
        assert {"mb", "chunk", "stage", "phase", "step"} <= set(e.args)
        anchor = steps[e.args["step"]]
        assert anchor.ts <= e.ts and e.end <= anchor.end + 1e-9
    # forward events strictly precede their mirrored backward per step
    for s in range(2):
        fs = [e for e in f_ev if e.args["step"] == s]
        bs = [e for e in b_ev if e.args["step"] == s]
        assert max(e.end for e in fs) <= min(e.ts for e in bs) + 1e-12


# -------------------------------------------------- Session / CLI thread ----


def test_cli_train_pp2_smoke():
    from repro.app.cli import run as cli_run

    res = cli_run([
        "train", "--arch", "qwen2-0.5b", "--smoke", "--steps", "2",
        "--set", "train.seq_len=32", "--set", "train.global_batch=4",
        "--set", "parallel.pp=2", "--set", "parallel.n_micro=2",
        "--set", "parallel.schedule=wave",
    ])
    par = res["parallel"]
    assert par["pp"] == 2 and par["n_micro"] == 2
    assert par["wave"] >= 1                  # planner filled the wave in
    assert par["mesh"] == {"stage": 2, "data": 1, "model": 1}
    assert len(res["history"]) >= 1
    assert all(np.isfinite(h["loss"]) for h in res["history"])


def test_session_rejects_indivisible_micro():
    from repro.app.cli import run as cli_run

    with pytest.raises(SystemExit, match="not divisible"):
        cli_run([
            "train", "--arch", "qwen2-0.5b", "--smoke", "--steps", "1",
            "--set", "train.global_batch=4", "--set", "parallel.pp=2",
            "--set", "parallel.n_micro=3",
        ])


# ------------------------------------------------- schedule satellites ------


def test_sched_wave_edge_cases():
    # wave > n_micro clamps to BFC
    assert sched_wave(4, 2, 9) == sched_wave(4, 2, 4) == sched_bfc(4, 2)
    # single microbatch: every wave width degenerates to DFC
    assert sched_wave(1, 3, 1) == sched_wave(1, 3, 7) == sched_dfc(1, 3)
    # non-dividing wave: trailing partial wave, full coverage exactly once
    steps = sched_wave(5, 2, 2)
    assert len(steps) == 2 * 5 * 2
    for kind in ("F", "B"):
        seen = [(m, c) for k, m, c in steps if k == kind]
        assert sorted(seen) == [(m, c) for m in range(5) for c in range(2)]
    # last (partial) wave is microbatch 4 alone, depth-first
    assert steps[-4:] == [("F", 4, 0), ("F", 4, 1), ("B", 4, 1), ("B", 4, 0)]


@pytest.mark.parametrize("order_fn,n_micro,n_chunks,S", [
    (lambda: sched_dfc(3, 2), 3, 2, 4),
    (lambda: sched_bfc(4, 2), 4, 2, 2),
    (lambda: sched_wave(5, 2, 2), 5, 2, 3),
    (lambda: sched_wave(4, 3, 4), 4, 3, 2),
])
def test_build_time_table_legality(order_fn, n_micro, n_chunks, S):
    table = build_time_table(order_fn(), S, n_chunks, n_micro)
    run_act = np.asarray(table.run_act)
    run_m = np.asarray(table.run_m)
    run_c = np.asarray(table.run_c)
    T = run_act.shape[0]
    when = {}
    for t in range(T):
        for s in range(S):
            if run_act[t, s]:
                key = (int(run_m[t, s]), int(run_c[t, s]), s)
                assert key not in when, f"{key} ran twice"
                when[key] = t
    # every (m, c) runs exactly once per stage
    assert len(when) == n_micro * n_chunks * S
    # a block runs only after its producer ran (receive precedes run)
    for (m, c, s), t in when.items():
        if s > 0:
            assert when[(m, c, s - 1)] < t
        elif c > 0:
            assert when[(m, c - 1, S - 1)] < t
    assert 0.0 <= bubble_fraction(table) < 1.0


def test_emit_pipeline_events_matches_table_occupancy():
    table = build_time_table(sched_dfc(3, 2), 2, 2, 3)
    events = []
    emit_pipeline_events(events, table, ts=10.0, wall=1.0)
    f = [e for e in events if e.name == "pp_F"]
    assert len(f) == int(np.asarray(table.run_act).sum())
    assert all(10.0 <= e.ts and e.end <= 11.0 + 1e-9 for e in events)


# ------------------------------------------------------- mesh satellite -----


@pytest.mark.parametrize("data,model", [(4, 2), (2, 4)])
def test_host_mesh_auto_axes_take_sharding_constraints(data, model):
    """Host meshes carry Auto axes, so the logical-axis rules can constrain
    activations with ``with_sharding_constraint`` in either orientation."""
    from jax.sharding import AxisType

    from repro.parallel.sharding import DEFAULT_RULES, axis_rules, shard_act

    if len(jax.devices()) < data * model:
        pytest.skip(f"needs {data * model} host devices")
    m = make_host_mesh(data=data, model=model)
    assert dict(m.shape) == {"data": data, "model": model}
    assert set(m.axis_types) == {AxisType.Auto}
    x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
    with m, axis_rules(m, DEFAULT_RULES):
        y = jax.jit(lambda a: shard_act(a * 2, ("batch", "mlp")))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x) * 2)
    assert y.sharding.spec == jax.sharding.PartitionSpec("data", "model")


def test_pipeline_mesh_too_few_devices():
    with pytest.raises(ValueError, match="devices"):
        make_pipeline_mesh(len(jax.devices()) + 1)
