"""Substrate tests: data pipeline determinism, checkpoint atomicity/restore,
failover supervisor, mitigation policy, gradient compression."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer, latest_step, restore, save
from repro.core.tracing.detect import Diagnosis
from repro.data.pipeline import DataConfig, SyntheticTokens, make_pipeline
from repro.ft.compress import GradCompressor
from repro.ft.failover import TrainSupervisor
from repro.ft.mitigation import MitigationAction, MitigationPolicy
from repro.launch.mesh import auto_mesh


# ------------------------------------------------------------------ data ---


def test_data_step_indexed_determinism():
    cfg = DataConfig(vocab_size=1000, seq_len=64, global_batch=4, seed=7)
    ds = SyntheticTokens(cfg)
    a, b = ds.batch_at(13), ds.batch_at(13)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = ds.batch_at(14)
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert a["tokens"].shape == (4, 64)
    assert a["targets"].dtype == np.int32


def test_data_host_sharding_partitions_batch():
    base = DataConfig(vocab_size=1000, seq_len=16, global_batch=8, n_hosts=2, host_id=0)
    h0 = SyntheticTokens(base).batch_at(0)
    h1 = SyntheticTokens(DataConfig(**{**base.__dict__, "host_id": 1})).batch_at(0)
    assert h0["tokens"].shape == (4, 16)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_prefetcher_resumes_at_step():
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=2)
    ds = SyntheticTokens(cfg)
    pf = make_pipeline(cfg, start_step=5)
    got = pf.next()
    pf.close()
    np.testing.assert_array_equal(got["tokens"], ds.batch_at(5)["tokens"])


# ------------------------------------------------------------ checkpoint ---


def _toy_state(x=1.0):
    return {"params": {"w": jnp.full((4, 4), x), "b": jnp.zeros((4,))},
            "opt": {"m": jnp.ones((4, 4)) * 2, "step": jnp.int32(3)}}


def test_checkpoint_roundtrip(tmp_path):
    st = _toy_state(1.5)
    save(st, 10, tmp_path, metadata={"arch": "toy"})
    assert latest_step(tmp_path) == 10
    restored, manifest = restore(tmp_path, jax.tree.map(lambda x: x, st))
    assert manifest["metadata"]["arch"] == "toy"
    np.testing.assert_array_equal(restored["params"]["w"], st["params"]["w"])
    assert int(restored["opt"]["step"]) == 3


def test_checkpoint_atomic_no_partial_visible(tmp_path):
    # a stale .tmp dir must never be listed as a restorable step
    (tmp_path / "step_00000099.tmp").mkdir(parents=True)
    save(_toy_state(), 5, tmp_path)
    assert latest_step(tmp_path) == 5


def test_checkpointer_async_and_prune(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for s in (1, 2, 3):
        ck.save_async(_toy_state(float(s)), s)
    ck.wait()
    steps = sorted(p.name for p in tmp_path.iterdir() if p.name.startswith("step_"))
    assert steps == ["step_00000002", "step_00000003"]


def test_elastic_restore_with_new_sharding(tmp_path):
    st = _toy_state(2.0)
    save(st, 1, tmp_path)
    mesh = auto_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), st)
    restored, _ = restore(tmp_path, st, shardings=sh)
    assert restored["params"]["w"].sharding == NamedSharding(mesh, P())


# -------------------------------------------------------------- failover ---


def test_supervisor_recovers_from_injected_failure(tmp_path):
    calls = {"n": 0}

    def step_fn(state, batch):
        calls["n"] += 1
        if calls["n"] == 7:  # one-shot injected failure after ckpt at step 4
            raise RuntimeError("simulated device loss")
        return {"w": state["w"] + batch["x"]}, {"loss": jnp.float32(0.0)}

    sup = TrainSupervisor(
        step_fn=step_fn,
        make_batch=lambda step: {"x": jnp.float32(step)},
        ckpt_dir=str(tmp_path),
        ckpt_every=4,
        max_restarts=2,
    )
    state, step = sup.run({"w": jnp.float32(0.0)}, n_steps=10)
    assert step == 10
    # deterministic data => final state identical to an uninterrupted run
    expect = sum(range(10))
    assert float(state["w"]) == expect


# ------------------------------------------------------------- mitigation --


def _diag(slow_frac, n_inst=50, ranks=(3,)):
    return Diagnosis(
        slow_ranks=list(ranks), candidate_ranks=list(ranks), degraded_links=[],
        rank_scores={r: {"slow_op_frac": slow_frac, "late_start_frac": 0.9}
                     for r in ranks},
        evidence={"n_instances": n_inst},
    )


def test_policy_thresholds():
    pol = MitigationPolicy()
    act, _ = pol.decide(_diag(0.4))
    assert act is MitigationAction.REPLAN
    act, _ = pol.decide(_diag(0.9))
    assert act is MitigationAction.EXCLUDE_RESTART
    act, _ = pol.decide(Diagnosis([], [], [], evidence={"n_instances": 50}))
    assert act is MitigationAction.NONE
    act, _ = pol.decide(_diag(0.9, n_inst=2))
    assert act is MitigationAction.NONE  # insufficient evidence


# -------------------------------------------------------------- compress ---


def test_compression_error_bounded():
    comp = GradCompressor(block=64, bits=8)
    g = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    err0 = jnp.zeros((1000,))
    deq, err = comp.apply({"g": g}, {"g": err0})
    rel = float(jnp.linalg.norm(deq["g"] - g) / jnp.linalg.norm(g))
    assert rel < 0.02
    # wire volume: ~4x smaller than bf16
    c, base = comp.wire_bytes({"g": g})
    assert c < base


def test_error_feedback_removes_bias():
    """Sum of compressed grads with feedback converges to the true sum."""
    comp = GradCompressor(block=32, bits=4)  # coarse to make bias visible
    rng = jax.random.PRNGKey(1)
    g_true = jax.random.normal(rng, (256,)) * 1e-3
    total_fb = jnp.zeros_like(g_true)
    total_nofb = jnp.zeros_like(g_true)
    err = {"g": jnp.zeros_like(g_true)}
    for _ in range(50):
        deq, err = comp.apply({"g": g_true}, err)
        total_fb = total_fb + deq["g"]
        deq2, _ = comp.apply({"g": g_true}, {"g": jnp.zeros_like(g_true)})
        total_nofb = total_nofb + deq2["g"]
    true_total = g_true * 50
    err_fb = float(jnp.linalg.norm(total_fb - true_total))
    err_nofb = float(jnp.linalg.norm(total_nofb - true_total))
    assert err_fb < err_nofb
