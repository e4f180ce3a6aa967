"""repro.app: RunConfig layering, Session plugins, CLI subcommands, shims.

Covers the acceptance surface of the unified entry point:
  * RunConfig layering (defaults -> workload -> JSON -> --set -> flags) with
    typed coercion and loud failure on typos;
  * CLI smoke runs for every subcommand on CPU smoke configs;
  * plugin on/off equivalence: module plugins must not perturb numerics —
    train-loss trajectories and greedy serve tokens are identical with
    modules disabled vs the seed code paths (and with passive modules on);
  * the deprecation shims (`repro.launch.train/serve`) still run and defer
    to the same implementation.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.app import (
    PLUGIN_REGISTRY,
    ModulePlugin,
    RunConfig,
    Session,
    build_run_config,
)
from repro.app.cli import run as cli_run
from repro.app.config import apply_sets, set_by_path

ROOT = Path(__file__).resolve().parent.parent
ARCH = "qwen2-0.5b"

# keep jitted-step compiles tiny: the equivalence/CLI tests only care about
# wiring, not model scale
TINY_TRAIN = ["--set", "train.seq_len=32", "--set", "train.global_batch=2"]


# ---------------------------------------------------------------------------
# RunConfig layering
# ---------------------------------------------------------------------------


class TestRunConfig:
    def test_defaults_and_workload_layer(self):
        cfg = RunConfig.for_workload("train")
        assert cfg.workload == "train"
        assert cfg.modules == ("scan", "metrics")  # observability on by default
        assert cfg.train.steps == 100
        cfg = RunConfig.for_workload("dryrun")
        assert cfg.modules == ()             # nothing to attach to

    def test_set_by_path_coerces_types(self):
        cfg = RunConfig.for_workload("serve")
        set_by_path(cfg, "serve.spec_k", "6")
        set_by_path(cfg, "serve.rate", "2.5")
        set_by_path(cfg, "serve.continuous", "true")
        set_by_path(cfg, "serve.prompt_lens", "8,16")
        assert cfg.serve.spec_k == 6
        assert cfg.serve.rate == 2.5
        assert cfg.serve.continuous is True
        assert cfg.serve.prompt_lens == (8, 16)

    def test_unknown_key_fails_loudly(self):
        cfg = RunConfig.for_workload("train")
        with pytest.raises(KeyError):
            set_by_path(cfg, "train.bogus", "1")
        with pytest.raises(KeyError):
            set_by_path(cfg, "nosection.x", "1")
        with pytest.raises(KeyError):
            set_by_path(cfg, "train", "1")   # a section, not a field

    def test_apply_sets_parses_key_value(self):
        cfg = RunConfig.for_workload("train")
        apply_sets(cfg, ["train.lr=1e-3", "smoke=1"])
        assert cfg.train.lr == pytest.approx(1e-3)
        assert cfg.smoke is True
        with pytest.raises(ValueError):
            apply_sets(cfg, ["no_equals_sign"])

    def test_json_then_sets_then_flags_layering(self, tmp_path):
        p = tmp_path / "run.json"
        p.write_text(json.dumps({
            "arch": ARCH,
            "train": {"steps": 7, "lr": 9e-4},
            "modules": ["scan", "scope"],
        }))
        cfg = build_run_config(
            "train", config_json=str(p),
            sets=["train.lr=5e-4"],          # --set overrides JSON
            train__steps=3,                   # explicit flag overrides both
        )
        assert cfg.arch == ARCH
        assert cfg.train.steps == 3
        assert cfg.train.lr == pytest.approx(5e-4)
        assert cfg.modules == ("scan", "scope")

    def test_modules_none_and_validation(self):
        cfg = build_run_config("train", sets=["modules=none"])
        assert cfg.modules == ()
        with pytest.raises(ValueError):
            build_run_config("train", sets=["modules=scan,notamodule"])

    def test_registry_has_all_four_modules(self):
        assert set(PLUGIN_REGISTRY) >= {"scan", "scope", "fbd", "dpp"}


# ---------------------------------------------------------------------------
# CLI smoke: every subcommand on a CPU smoke config
# ---------------------------------------------------------------------------


class TestCLISmoke:
    def test_train_subcommand(self):
        res = cli_run(["train", "--arch", ARCH, "--smoke", "--steps", "2",
                       "--modules", "scan,scope,dpp,fbd", *TINY_TRAIN])
        assert len(res["history"]) >= 1
        assert res["scan"]["events"] >= 3           # init + 2 steps
        assert res["dpp"]["schedule"]
        assert res["fbd"]["speedup"] > 0
        assert any("mlp_hidden" in k for k in res["scope"]["captured"])

    def test_serve_subcommand_continuous(self):
        res = cli_run(["serve", "--arch", ARCH, "--smoke", "--continuous",
                       "--requests", "4", "--max-new", "4", "--rate", "1000"])
        assert res["serve_metrics"]["generated_tokens"] > 0
        assert res["scan"]["events"] > 0            # serving traces via scan

    def test_serve_scope_captures_surface(self):
        """MegaServe attaches captures per generated token; the scope plugin
        must see them like training captures."""
        res = cli_run(["serve", "--arch", ARCH, "--smoke", "--continuous",
                       "--requests", "3", "--max-new", "4", "--rate", "1000",
                       "--modules", "scan,scope"])
        assert any("mlp_hidden" in k for k in res["scope"]["captured"])

    def test_serve_subcommand_static(self):
        res = cli_run(["serve", "--arch", ARCH, "--smoke",
                       "--batch", "2", "--prompt-len", "8", "--max-new", "4"])
        assert res["serve_metrics"]["decode_s"] >= 0

    def test_trace_subcommand(self, tmp_path):
        out = tmp_path / "scan"
        res = cli_run(["trace", "--out", str(out), "--slow-rank", "3",
                       "--iters", "2"])
        assert res["truth"]["detected"] is True
        assert (out / "trace.json").exists()
        assert (out / "diagnosis.json").exists()

    def test_trace_out_shared_across_workloads(self, tmp_path):
        """--trace-out works for serving too (satellite: chrome export is
        hoisted out of the train launcher into the shared CLI)."""
        t = tmp_path / "serve_trace.json"
        cli_run(["serve", "--arch", ARCH, "--smoke", "--continuous",
                 "--requests", "4", "--max-new", "6", "--rate", "1000",
                 "--trace-out", str(t)])
        doc = json.loads(t.read_text())
        names = {e["name"] for e in doc["traceEvents"]}
        assert "prefill" in names and "decode" in names

    def test_dryrun_subcommand_subprocess(self, tmp_path):
        """dryrun must run from a fresh process (XLA_FLAGS ordering); the
        host-mesh smoke path lowers+compiles a real cell on CPU."""
        env = dict(os.environ)
        env["PYTHONPATH"] = str(ROOT / "src") + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        env["REPRO_DRYRUN_DEVICES"] = "8"
        # keep JAX's persistent cache out of the checkout
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
        proc = subprocess.run(
            [sys.executable, "-m", "repro", "dryrun", "--arch", ARCH,
             "--shape", "train_4k", "--smoke", "--host-mesh",
             "--out", str(tmp_path)],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=560,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        (cell,) = tmp_path.glob("*.json")
        res = json.loads(cell.read_text())
        assert res["flops_per_device"] > 0
        assert res["memory"]["peak_est_bytes"] > 0


# ---------------------------------------------------------------------------
# plugin on/off equivalence vs the seed code paths
# ---------------------------------------------------------------------------


def _session_train_losses(modules, steps=3):
    cfg = RunConfig.for_workload("train", arch=ARCH, smoke=True,
                                 modules=modules)
    cfg.train.steps = steps
    cfg.train.seq_len = 32
    cfg.train.global_batch = 2
    cfg.train.log_every = 1
    _, history = Session(cfg).run()
    return [h["loss"] for h in history]


class TestEquivalence:
    def test_train_loss_identical_modules_on_off_and_seed(self):
        from repro.app.session import pick_mesh
        from repro.configs import get_config
        from repro.data.pipeline import DataConfig
        from repro.parallel.profiles import rules_for
        from repro.parallel.sharding import axis_rules
        from repro.train.loop import LoopConfig, train
        from repro.train.optim import OptimizerConfig

        steps = 3
        off = _session_train_losses((), steps)
        on = _session_train_losses(("scan", "scope", "dpp", "fbd"), steps)

        # the seed path: hand-wire what the old launcher did — the same
        # mesh + sharding rules, the loop called directly (sharding changes
        # reduction order, so the mesh context must match to compare)
        mcfg = get_config(ARCH, smoke=True)
        mesh = pick_mesh("auto")
        with mesh, axis_rules(mesh, rules_for(mcfg, "train")):
            _, hist = train(
                mcfg,
                OptimizerConfig(lr=3e-4, warmup_steps=5, total_steps=steps),
                DataConfig(vocab_size=mcfg.vocab_size, seq_len=32,
                           global_batch=2),
                LoopConfig(n_steps=steps, log_every=1),
            )
        seed = [h["loss"] for h in hist]

        # modules disabled must be bit-identical to the seed path
        np.testing.assert_array_equal(off, seed)
        # passive modules must not perturb training (probe capture outputs
        # may legally alter XLA fusion, so allow float-noise tolerance)
        np.testing.assert_allclose(on, seed, rtol=1e-5, atol=1e-6)

    def test_serve_tokens_identical_modules_on_off_and_seed(self):
        import jax

        from repro.configs import get_config
        from repro.models import get_model
        from repro.serve import MegaServe
        from repro.serve.server import make_poisson_workload

        def run_session(modules):
            cfg = RunConfig.for_workload("serve", arch=ARCH, smoke=True,
                                         modules=modules)
            cfg.serve.continuous = True
            cfg.serve.requests = 4
            cfg.serve.max_new = 6
            cfg.serve.rate = 1000.0
            outs, _ = Session(cfg).run()
            return outs

        off = run_session(())
        on = run_session(("scan", "dpp", "fbd"))

        # seed path: hand-wired MegaServe over the same workload
        mcfg = get_config(ARCH, smoke=True)
        m = get_model(mcfg)
        params = m.init(mcfg, jax.random.PRNGKey(0))
        specs, prompts, serve_cfg = make_poisson_workload(
            mcfg, n=4, rate=1000.0, prompt_lens=(16, 32, 64, 128, 256),
            max_new_range=(1, 6), num_slots=4, block_size=16,
            num_blocks=0, seed=0,
        )
        srv = MegaServe(mcfg, params, serve_cfg)
        for s in specs:
            srv.submit(prompts[s.rid], s.max_new, arrival=s.arrival)
        seed = srv.drain()

        assert off == seed
        assert on == seed


# ---------------------------------------------------------------------------
# Session plumbing: hooks, from_session, custom plugins
# ---------------------------------------------------------------------------


class TestSessionPlumbing:
    def test_step_hooks_fire_per_step(self):
        calls = []

        class Spy(ModulePlugin):
            name = "spy"

            def wrap_step(self, fn):
                calls.append("wrap")
                return fn

            def on_step(self, session, events, metrics):
                calls.append(("step", [e.name for e in events]))

            def finalize(self, session):
                return {"steps_seen": sum(1 for c in calls if c != "wrap")}

        cfg = RunConfig.for_workload("train", arch=ARCH, smoke=True)
        cfg.train.steps = 2
        cfg.train.seq_len = 32
        cfg.train.global_batch = 2
        s = Session(cfg, plugins=[Spy(cfg)])
        s.run()
        assert calls.count("wrap") == 1
        step_calls = [c for c in calls if c != "wrap"]
        assert len(step_calls) == 2
        # tracer disabled without the scan plugin -> no events observed,
        # but the hook still fires uniformly
        assert s.results["spy"]["steps_seen"] == 2

    def test_scan_plugin_owns_tracer_and_from_session(self):
        import jax

        from repro.models import get_model
        from repro.serve.scheduler import ServeConfig

        cfg = RunConfig.for_workload("serve", arch=ARCH, smoke=True)
        s = Session(cfg)
        assert s.tracer.enabled        # scan is in the default module set
        mcfg = s.model_cfg
        params = get_model(mcfg).init(mcfg, jax.random.PRNGKey(0))
        srv_cfg = ServeConfig(num_slots=2, num_blocks=17, block_size=16,
                              max_blocks_per_slot=8)
        from repro.serve import MegaServe

        srv = MegaServe.from_session(s, params, srv_cfg)
        assert srv.tracer is s.tracer
        assert srv.collector is s.collector

    def test_train_tracer_default_unified(self):
        """Satellite: train() no longer silently disables tracing — its
        default matches MegaServe's (enabled)."""
        import inspect

        from repro.train.loop import train as train_fn

        src = inspect.getsource(train_fn)
        assert "enabled=True" in src and "enabled=False" not in src


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------


class TestShims:
    @pytest.fixture(autouse=True)
    def _jax_cache_env(self, monkeypatch, tmp_path):
        # the shims enter through cli.main, which turns on JAX's persistent
        # cache unless this is set: keep it out of the checkout and of the
        # process-wide config later tests compile under
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def test_launch_train_shim(self, capsys):
        from repro.launch.train import main as legacy_train

        with pytest.warns(DeprecationWarning, match="python -m repro train"):
            legacy_train(["--arch", ARCH, "--smoke", "--steps", "2",
                          "--seq-len", "32", "--global-batch", "2"])
        out = capsys.readouterr().out
        assert "loss" in out

    def test_launch_serve_shim(self, capsys):
        from repro.launch.serve import main as legacy_serve

        with pytest.warns(DeprecationWarning, match="python -m repro serve"):
            legacy_serve(["--arch", ARCH, "--smoke", "--continuous",
                          "--requests", "2", "--max-new", "2",
                          "--rate", "1000"])
        out = capsys.readouterr().out
        assert "tokens_per_s" in out
