"""CPU rehearsal of the serving runner at smoke size (Pallas in interpret
mode where it runs): a whole run through ``bench/run.py``'s ``run`` with the
look for a chip skipped, its result line, the control that has to come out
above the limit, and faults planted in the timed path that have to come out
as not correct."""

from __future__ import annotations

import importlib.util
import json

import pytest

from conftest import BENCH, load_run

from benchlib.device import Peak

CPU_PEAK = Peak("cpu-test", 1e12, 1e11, 1e10, "test value, not a device")
SEED = 2**33 + 5


def run_smoke(root, trace: int, seed: int = SEED) -> dict:
    run = load_run()
    args = run.parse(["--workload", "serve-smoke", "--seed", str(seed),
                      "--seconds", "2", "--trace", str(trace)])
    return run.run(args, root=root, require_chip=False, peak_override=CPU_PEAK)


def test_end_to_end_run(smoke_root):
    out = run_smoke(smoke_root, 0)
    json.dumps(out)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] == 24
    assert set(out["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"mean_logit_gap", "unfinished_requests"}


def test_traced_run(smoke_root):
    out = run_smoke(smoke_root, 1)
    assert out["correct"] is True
    # the CPU trace has no TPU plane: device readers find nothing and are left out
    assert set(out["metrics"]) == {"queue_wait_p50_ms.serve", "mfu.prefill",
                                   "mfu.decode"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_step_kind_is_told_by_where_the_pool_sits():
    from types import SimpleNamespace

    from benchlib.serve import step_kind

    srv = SimpleNamespace(pool={"k": 0})
    assert step_kind(srv, ("p", srv.pool, "tables", "tokens", "pos")) == "decode"
    assert step_kind(srv, ("p", "tokens", 7, srv.pool, 0, "phys")) == "prefill"
    # chunked prefill (6 arguments, the pool second) and a call that does not
    # hold this server's pool are not counted as either
    with pytest.raises(RuntimeError):
        step_kind(srv, ("p", srv.pool, "tables", "toks", "pos", 0))
    with pytest.raises(RuntimeError):
        step_kind(srv, ("p", {"k": 0}, "tables", "tokens", "pos"))


class Fault:
    """Session plugin that breaks the jitted decode step underneath."""

    name = "fault"

    def __init__(self, kind: str):
        self.kind = kind

    def setup(self, session):
        return None

    def on_step(self, session, events, metrics):
        return None

    def finalize(self, session):
        return {}

    def wrap_step(self, fn):
        import jax
        import jax.numpy as jnp

        def call(*args):
            if len(args) != 5:                 # prefill: left alone
                return fn(*args)
            before = jax.tree.map(jnp.copy, args[1])   # the pool is donated
            pool, tok, caps = fn(*args)
            if self.kind == "token":           # a token altered where produced
                tok = jnp.where(tok > 2, tok - 1, tok + 1)
            elif self.kind == "state":         # the step leaves its KV unwritten
                pool = before
            return pool, tok, caps

        return call


@pytest.mark.parametrize("kind", ["token", "state"])
def test_fault_in_the_timed_path_is_not_correct(smoke_root, monkeypatch, kind):
    import repro.app.plugins as plugins

    real = plugins.build_plugins
    monkeypatch.setattr(plugins, "build_plugins",
                        lambda *a, **k: real(*a, **k) + [Fault(kind)])
    out = run_smoke(smoke_root, 0)
    assert out["correct"] is False
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_comes_out_above_the_limit(smoke_root):
    """The float8 control fails the smoke cell's limit on three seeds, and
    the program passes it (``bench/tools/control.py`` at smoke size)."""
    from benchlib import serve
    from benchlib.spec import load_cell
    from benchlib.traffic import serve_requests
    from benchlib.weights import make_weights

    spec = importlib.util.spec_from_file_location("control", BENCH / "tools/control.py")
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    cell = load_cell("serve-smoke", smoke_root)
    limit = cell.config["correct"]["mean_logit_gap"]
    session, srv, dense, _ = serve.build(cell, 1, trace=False)
    for seed in (1, 2, SEED):
        srv.params = make_weights(dense, seed, "bfloat16")
        reqs = serve_requests(cell.traffic, seed, 2.0, dense.vocab)
        serve.run_window(session, srv, reqs, 2.0)
        got = control.readings(srv, reqs, srv.params, dense, seed,
                               cell.traffic["check"]["sample_requests"])
        assert got["finished"] == got["due"] == len(reqs)
        assert got["program_mean_gap"] <= limit < got["control_mean_gap"]
