"""Fault-tolerance loop: chaos injection, mitigation decisions and their
execution in the supervised train loop, checkpoint atomicity/elasticity,
in-band guards, and the end-to-end chaos acceptance run."""

import json
import math
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer, latest_step, restore, save
from repro.core.tracing.detect import Diagnosis
from repro.ft import (
    ChaosInjector,
    ChaosSpec,
    FtController,
    FtOptions,
    MitigationAction,
    MitigationPolicy,
    TrainSupervisor,
    parse_link,
    simulate_policy,
)
from repro.launch.mesh import auto_mesh
from repro.obs.detector import DetectionUpdate

TINY = ["--arch", "qwen2-0.5b", "--smoke",
        "--seq-len", "32", "--global-batch", "2"]


# ------------------------------------------------------------ chaos spec ---


class TestChaosSpec:
    def test_parse_link(self):
        assert parse_link("0-1") == (0, 1)
        assert parse_link("12-3") == (12, 3)
        with pytest.raises(ValueError, match="src-dst"):
            parse_link("nope")

    def test_active_and_needs_restore(self):
        assert not ChaosSpec().active
        assert ChaosSpec(nan_at_step=2).active
        assert ChaosSpec(slow_rank_from=0).active
        assert ChaosSpec(degrade_link="0-1").active
        assert ChaosSpec(crash_at_step=5).needs_restore
        assert not ChaosSpec(nan_at_step=5).needs_restore

    def test_to_fault_model(self):
        fm = ChaosSpec(slow_rank_from=0, slow_rank=2, slow_factor=0.4,
                       degrade_link="1-0", degrade_factor=0.2).to_fault_model()
        assert fm.compute_slowdown == {2: 0.4}
        assert fm.link_slowdown == {(1, 0): 0.2}
        # crash/NaN are recovery faults: no offline timeline analogue
        assert ChaosSpec(crash_at_step=3).to_fault_model().compute_slowdown == {}

    def test_injector_crash_fires_once(self):
        inj = ChaosInjector(ChaosSpec(crash_at_step=5))
        assert not inj.crash_due(4)
        assert inj.crash_due(5)
        assert not inj.crash_due(5)  # replay after restore: no re-fire

    def test_injector_nan_poisons_batch_once(self):
        inj = ChaosInjector(ChaosSpec(nan_at_step=3))
        batch = {"tokens": np.zeros((2, 4), np.int32),
                 "loss_mask": np.ones((2, 4), np.float32)}
        clean = inj.poison_batch(batch, 2)
        assert clean is batch
        poisoned = inj.poison_batch(batch, 3)
        assert np.isnan(poisoned["loss_mask"]).all()
        assert not np.isnan(batch["loss_mask"]).any()  # original untouched
        assert inj.poison_batch(batch, 3) is batch  # one-shot

    def test_slow_active_window(self):
        inj = ChaosInjector(ChaosSpec(slow_rank_from=4))
        assert not inj.slow_active(3)
        assert inj.slow_active(4) and inj.slow_active(100)
        assert not ChaosInjector(ChaosSpec()).slow_active(0)


# ---------------------------------------------- offline policy evaluation ---


class TestSimulatePolicy:
    def test_healthy_run_decides_none(self):
        _, action, info = simulate_policy(ChaosSpec())
        assert action is MitigationAction.NONE
        assert info["reason"] == "healthy"

    def test_hard_straggler_decides_exclude(self):
        diag, action, info = simulate_policy(
            ChaosSpec(slow_rank_from=0, slow_rank=1, slow_factor=0.5))
        assert action is MitigationAction.EXCLUDE_RESTART
        assert 1 in diag.slow_ranks
        assert info["severity"] >= 0.7

    def test_degraded_link_decides_replan(self):
        diag, action, _ = simulate_policy(ChaosSpec(degrade_link="0-1"))
        assert action is MitigationAction.REPLAN
        assert (0, 1) in {tuple(l) for l in diag.degraded_links}


# ------------------------------------------------------------ controller ---


def _update(step, *, ranks=(), links=(), frac=0.9, n_inst=50):
    diag = Diagnosis(
        slow_ranks=list(ranks), candidate_ranks=list(ranks),
        degraded_links=[tuple(l) for l in links],
        rank_scores={r: {"slow_op_frac": frac} for r in ranks},
        evidence={"n_instances": n_inst},
    )
    return DetectionUpdate(step=step, diagnosis=diag)


class TestFtController:
    def test_decision_lands_once_per_signature(self):
        c = FtController()
        c.on_detection(_update(8, ranks=(1,)))
        c.on_detection(_update(12, ranks=(1,)))  # standing diagnosis re-confirmed
        assert len(c.poll()) == 1
        assert c.poll() == []  # drained
        events = [t["event"] for t in c.timeline]
        assert events == ["decide:exclude"]

    def test_excluded_ranks_not_redecided(self):
        c = FtController()
        c.excluded.add(1)
        c.on_detection(_update(8, ranks=(1,)))  # stale sliding window
        assert c.poll() == []
        c.on_detection(_update(12, ranks=(1, 3)))  # but a NEW rank still acts
        (act,) = c.poll()
        assert act.slow_ranks == (3,)

    def test_insufficient_evidence_is_none(self):
        c = FtController()
        c.on_detection(_update(4, ranks=(1,), n_inst=3))
        assert c.poll() == [] and c.detections == 1

    def test_soft_straggler_and_link_decide_replan(self):
        c = FtController()
        c.on_detection(_update(8, ranks=(2,), frac=0.4))
        (act,) = c.poll()
        assert act.kind == "replan" and act.slow_ranks == (2,)
        c.on_detection(_update(8, links=((0, 1),)))
        (act,) = c.poll()
        assert act.kind == "replan" and act.degraded_links == ((0, 1),)

    def test_nan_guard(self):
        c = FtController(options=FtOptions(guard_action="rollback"))
        assert c.check_guards(3, 1.0, 0.5) is None
        assert c.check_guards(4, float("nan"), 0.5) == "rollback"
        assert c.guard_trips == 1
        assert c.timeline[-1]["event"] == "guard:rollback"

    def test_spike_guard_needs_history(self):
        c = FtController(options=FtOptions(guard_spike=10.0, guard_action="skip"))
        for s in range(8):
            assert c.check_guards(s, 1.0, 1.0) is None
        assert c.check_guards(8, 1.0, 50.0) == "skip"
        assert c.guard_trips == 1

    def test_report_shape(self):
        c = FtController()
        c.record_restart(6, 3, "InjectedCrash")
        c.record_rollback(9, 6)
        rep = c.report()
        assert rep["restarts"] == 1 and rep["rollbacks"] == 1
        assert [t["event"] for t in rep["timeline"]] == ["restart", "rollback"]
        assert rep["timeline"][0]["details"]["resumed_step"] == 3


# ------------------------------------- checkpoint atomicity + elasticity ---


def _toy_state(v=1.0):
    return {"params": {"w": jnp.full((4, 4), v, jnp.float32)},
            "step": jnp.int32(3)}


class TestCheckpointFailureModes:
    def test_crash_mid_save_leaves_only_tmp(self, tmp_path, monkeypatch):
        save(_toy_state(1.0), 2, tmp_path)
        import repro.checkpoint.checkpointer as ckpt_mod

        calls = {"n": 0}
        real_save = np.save

        def dying_save(path, arr):
            calls["n"] += 1
            if calls["n"] == 2:  # die mid-way through the leaf files
                raise OSError("disk gone")
            real_save(path, arr)

        monkeypatch.setattr(ckpt_mod.np, "save", dying_save)
        with pytest.raises(OSError):
            save(_toy_state(9.0), 5, tmp_path)
        monkeypatch.undo()
        # the half-written attempt is still a .tmp dir — never visible
        assert (tmp_path / "step_00000005.tmp").exists()
        assert not (tmp_path / "step_00000005").exists()
        assert latest_step(tmp_path) == 2
        restored, _ = restore(tmp_path, _toy_state())
        assert float(restored["params"]["w"][0, 0]) == 1.0
        # a retry over the stale .tmp succeeds
        save(_toy_state(9.0), 5, tmp_path)
        assert latest_step(tmp_path) == 5

    def test_bf16_elastic_restore_is_bit_identical(self, tmp_path):
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = jax.random.PRNGKey(0)
        st = {"w": jax.random.normal(key, (8, 16)).astype(jnp.bfloat16),
              "b": jax.random.normal(key, (16,)).astype(jnp.bfloat16)}
        save(st, 1, tmp_path)

        def bits(tree):
            return {k: np.asarray(v).view(np.uint16) for k, v in tree.items()}

        want = bits(st)
        # restore onto a replicated 1-device mesh and, when the host mesh
        # has more devices, onto a data-sharded one: same bits both ways
        meshes = [(auto_mesh((1,), ("data",)), P())]
        if len(jax.devices()) >= 2:
            meshes.append((auto_mesh((2,), ("data",)), P("data")))
        for mesh, pspec in meshes:
            sh = jax.tree.map(lambda _: NamedSharding(mesh, pspec), st)
            restored, _ = restore(tmp_path, st, shardings=sh)
            got = bits(restored)
            for k in want:
                np.testing.assert_array_equal(want[k], got[k])
            assert restored["w"].sharding == NamedSharding(mesh, pspec)

    def test_drain_returns_background_error_wait_raises(self, tmp_path, monkeypatch):
        import repro.checkpoint.checkpointer as ckpt_mod

        ck = Checkpointer(tmp_path)
        monkeypatch.setattr(
            ckpt_mod, "save",
            lambda *a, **k: (_ for _ in ()).throw(OSError("bg boom")))
        ck.save_async(_toy_state(), 1)
        err = ck.drain()
        assert isinstance(err, OSError)
        assert ck.drain() is None  # cleared, not sticky
        monkeypatch.undo()
        ck.save_async(_toy_state(), 2)
        ck.wait()  # healthy save: no raise
        assert latest_step(tmp_path) == 2


# -------------------------------------------------- supervisor satellites ---


class TestSupervisorRecovery:
    def test_history_truncated_after_rollback(self, tmp_path):
        calls = {"n": 0}

        def step_fn(state, batch):
            calls["n"] += 1
            if calls["n"] == 7:  # dies at step 6, after the ckpt at step 4
                raise RuntimeError("device loss")
            return {"w": state["w"] + batch["x"]}, {"loss": jnp.float32(0.0)}

        sup = TrainSupervisor(
            step_fn=step_fn, make_batch=lambda s: {"x": jnp.float32(s)},
            ckpt_dir=str(tmp_path), ckpt_every=4, max_restarts=2,
        )
        state, step = sup.run({"w": jnp.float32(0.0)}, n_steps=10)
        assert step == 10
        steps = [h["step"] for h in sup.history]
        # replayed rows replace the pre-rollback ones — no duplicates
        assert steps == sorted(set(steps)) == list(range(10))
        assert float(state["w"]) == sum(range(10))

    def test_background_save_error_does_not_mask_step_failure(
            self, tmp_path, monkeypatch):
        import repro.checkpoint.checkpointer as ckpt_mod

        save({"w": jnp.float32(0.0)}, 0, tmp_path)
        real_save = ckpt_mod.save
        fails = {"left": 1}

        def flaky_save(*a, **k):
            if fails["left"]:
                fails["left"] -= 1
                raise OSError("save died")
            return real_save(*a, **k)

        monkeypatch.setattr(ckpt_mod, "save", flaky_save)
        calls = {"n": 0}

        def step_fn(state, batch):
            calls["n"] += 1
            if calls["n"] == 6:  # after the (failed) background save at 4
                raise RuntimeError("step boom")
            return {"w": state["w"] + batch["x"]}, {}

        sup = TrainSupervisor(
            step_fn=step_fn, make_batch=lambda s: {"x": jnp.float32(s)},
            ckpt_dir=str(tmp_path), ckpt_every=4, max_restarts=2,
        )
        # the failed save is drained + logged, recovery proceeds from the
        # previous checkpoint (step 0) and the run still completes
        state, step = sup.run({"w": jnp.float32(0.0)}, n_steps=8)
        assert step == 8 and float(state["w"]) == sum(range(8))


# ----------------------------------------------- live guards + mitigation ---


def _run(extra):
    from repro.app.cli import run

    return run(["train", *TINY, *extra])


class TestGuards:
    def test_nan_rollback_recovers_exact_trajectory(self, tmp_path):
        clean = _run(["--steps", "8", "--modules", "metrics"])
        chaotic = _run([
            "--steps", "8", "--modules", "metrics,ft",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
            "--set", "ft.chaos.nan_at_step=4",
        ])
        ft = chaotic["ft"]
        assert ft["guard_trips"] == 1 and ft["rollbacks"] == 1
        events = [t["event"] for t in ft["timeline"]]
        assert events == ["guard:rollback", "rollback"]
        # rollback + step-indexed replay = the fault-free trajectory
        assert chaotic["history"][-1]["step"] == 8
        np.testing.assert_allclose(
            chaotic["history"][-1]["loss"], clean["history"][-1]["loss"],
            rtol=1e-5)

    def test_nan_skip_discards_update_without_restart(self):
        res = _run([
            "--steps", "6", "--modules", "metrics,ft",
            "--set", "ft.chaos.nan_at_step=3",
            "--set", "ft.guard_action=skip",
        ])
        ft = res["ft"]
        assert ft["guard_trips"] == 1
        assert ft["rollbacks"] == 0 and ft["restarts"] == 0
        assert res["history"][-1]["step"] == 6
        assert math.isfinite(res["history"][-1]["loss"])

    def test_guard_off_lets_nan_poison_the_run(self):
        res = _run([
            "--steps", "5", "--modules", "metrics,ft",
            "--set", "ft.chaos.nan_at_step=2",
            "--set", "ft.guard_nan=false", "--set", "ft.guard_action=skip",
        ])
        assert res["ft"]["guard_trips"] == 0
        assert math.isnan(res["history"][-1]["loss"])  # why the guard exists


class TestMitigationExecution:
    def test_insufficient_evidence_decides_none_via_plugin(self):
        # one detection pass at step 4: ~4 collective instances, below
        # ft.min_evidence=8 -> the policy verdict is NONE, nothing executes
        res = _run([
            "--steps", "6", "--modules", "scan,metrics,ft",
            "--detect-online", "--set", "scan.detect_every=4",
            "--set", "ft.chaos.slow_rank_from=0",
            "--set", "ft.chaos.slow_rank=1",
            "--set", "ft.chaos.slow_factor=0.5",
        ])
        ft = res["ft"]
        assert ft["detections"] >= 1
        assert not any(t["event"].startswith(("decide", "mitigate"))
                       for t in ft["timeline"]), ft["timeline"]
        assert ft["restarts"] == 0 and ft["excluded_ranks"] == []

    def test_degraded_link_switches_on_compression(self):
        res = _run([
            "--steps", "12", "--modules", "scan,metrics,ft",
            "--detect-online", "--set", "scan.detect_every=4",
            "--set", "ft.chaos.degrade_link=0-1",
        ])
        ft = res["ft"]
        assert ft["compression_on"] and ft["replans"] == 1
        events = [t["event"] for t in ft["timeline"]]
        assert "decide:replan" in events and "mitigate:compress_on" in events
        on = next(t for t in ft["timeline"]
                  if t["event"] == "mitigate:compress_on")
        d = on["details"]
        assert d["links"] == [[0, 1]]
        assert 0 < d["wire_bytes_per_sync"] < d["baseline_bytes_per_sync"]
        series = res["metrics"]["series"]
        assert 0 < series["ft.wire_bytes_compressed"] < series["ft.wire_bytes_baseline"]
        # compressed-sync steps still train (finite, decreasing-ish loss)
        assert math.isfinite(res["history"][-1]["loss"])

    def test_hard_straggler_excluded_via_restart(self, tmp_path):
        res = _run([
            "--steps", "14", "--modules", "scan,metrics,ft",
            "--detect-online", "--set", "scan.detect_every=4",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
            "--set", "ft.chaos.slow_rank_from=0",
            "--set", "ft.chaos.slow_rank=1",
            "--set", "ft.chaos.slow_factor=0.5",
        ])
        ft = res["ft"]
        assert ft["excluded_ranks"] == [1]
        assert ft["restarts"] == 1
        events = [t["event"] for t in ft["timeline"]]
        for e in ("decide:exclude", "mitigate:exclude", "restart"):
            assert e in events, ft["timeline"]
        assert res["history"][-1]["step"] == 14
        # detection happened online, before the run ended
        assert res["scan"]["online"]["first_detect_step"] <= 8

    def test_slow_stage_replans_pipeline_schedule(self):
        if len(jax.devices()) < 2:
            pytest.skip("pipeline replan needs >= 2 host devices")
        res = _run([
            "--steps", "14", "--global-batch", "4",
            "--pp", "2", "--n-micro", "2",
            "--modules", "scan,metrics,ft",
            "--detect-online", "--set", "scan.detect_every=4",
            "--set", "obs.rank_events=true", "--set", "obs.slow_rank=1",
            "--set", "obs.slow_factor=0.5",
            # soften the exclude threshold so a confirmed straggler REPLANs
            "--set", "ft.slow_frac_hard=1.1",
        ])
        ft = res["ft"]
        assert ft["replans"] == 1 and ft["restarts"] == 0
        rp = next(t for t in ft["timeline"]
                  if t["event"] == "mitigate:replan_schedule")
        assert rp["details"]["slow_ranks"] == [1]
        assert rp["details"]["wave"] >= 1
        assert res["history"][-1]["step"] == 14
        assert math.isfinite(res["history"][-1]["loss"])


# ------------------------------------------------- acceptance: full chaos ---


class TestChaosAcceptance:
    """ISSUE acceptance: crash at step k AND an induced straggler — the run
    completes all n steps, matches the fault-free final loss, and the
    mitigation timeline lands in results["ft"]."""

    @pytest.fixture(scope="class")
    def runs(self, tmp_path_factory):
        d = tmp_path_factory.mktemp("ft") / "ckpt"
        clean = _run(["--steps", "14", "--modules", "metrics"])
        chaotic = _run([
            "--steps", "14", "--modules", "scan,metrics,ft",
            "--detect-online", "--set", "scan.detect_every=4",
            "--ckpt-dir", str(d), "--ckpt-every", "3",
            "--set", "ft.chaos.crash_at_step=5",
            "--set", "ft.chaos.slow_rank_from=0",
            "--set", "ft.chaos.slow_rank=1",
            "--set", "ft.chaos.slow_factor=0.5",
        ])
        return clean, chaotic

    def test_completes_all_steps(self, runs):
        _, chaotic = runs
        assert chaotic["history"][-1]["step"] == 14

    def test_final_loss_matches_fault_free(self, runs):
        clean, chaotic = runs
        np.testing.assert_allclose(
            chaotic["history"][-1]["loss"], clean["history"][-1]["loss"],
            rtol=1e-5)

    def test_timeline_records_crash_restart_and_exclusion(self, runs):
        _, chaotic = runs
        ft = chaotic["ft"]
        assert ft["restarts"] >= 2  # the crash + the exclusion restart
        events = [t["event"] for t in ft["timeline"]]
        for e in ("restart", "decide:exclude", "mitigate:exclude"):
            assert e in events, ft["timeline"]
        crash = next(t for t in ft["timeline"] if t["event"] == "restart")
        assert crash["details"]["reason"] == "InjectedCrash"
        assert ft["excluded_ranks"] == [1]
        assert ft["detections"] > 0

    def test_counters_in_metrics_series(self, runs):
        _, chaotic = runs
        series = chaotic["metrics"]["series"]
        assert series["ft.restarts"] >= 2


class TestCliFlags:
    def test_chaos_crash_flag(self, tmp_path):
        res = _run([
            "--steps", "6", "--modules", "metrics,ft",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
            "--chaos-crash-at", "3", "--max-restarts", "2",
        ])
        ft = res["ft"]
        assert ft["restarts"] == 1
        assert ft["timeline"][-1]["details"]["reason"] == "InjectedCrash"
        assert res["history"][-1]["step"] == 6

    def test_crash_without_ckpt_dir_rejected(self):
        with pytest.raises(SystemExit, match="ckpt_dir"):
            _run(["--steps", "4", "--modules", "ft",
                  "--set", "ft.chaos.crash_at_step=2"])

    def test_max_restarts_bounds_recovery(self, tmp_path, monkeypatch):
        from repro.ft.chaos import InjectedCrash

        # every restart re-crashes (fired-set cleared) -> budget exhausts
        monkeypatch.setattr(ChaosInjector, "crash_due",
                            lambda self, step: step == 3)
        with pytest.raises(InjectedCrash):
            _run(["--steps", "6", "--modules", "ft",
                  "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
                  "--chaos-crash-at", "3", "--max-restarts", "2"])
