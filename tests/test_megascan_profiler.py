"""MegaScan in the JAX profiler's trace: every scope is also a
``megascan.<name>`` span carrying the tracer's rank and the scope's scalar
args; the serving tick's host work has spans of its own, nested in
``megascan.tick``; the jitted serving steps carry stable program names; and
a step compiled mid-serving after ``precompile()`` is counted."""

import itertools
from contextlib import nullcontext

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core.tracing import Tracer, TraceEvent, from_chrome, to_chrome
from repro.models import get_model
from repro.obs import MetricsRegistry
from repro.serve import MegaServe, ServeConfig

SCFG = ServeConfig(num_slots=2, block_size=8, num_blocks=17,
                   max_blocks_per_slot=4)


def profiled(tmp_path, body) -> list[tuple]:
    """Run ``body`` under the profiler; its ``megascan.*`` host spans as
    (name, start_ns, end_ns, stats), sorted by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = max(tmp_path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("megascan."):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda x: x[1])


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2-0.5b", smoke=True).replace(
        compute_dtype="float32", attn_kv_chunk=4096
    )
    return cfg, get_model(cfg).init(cfg, jax.random.PRNGKey(0))


def prompts(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, cfg.vocab_size, size=size).tolist()
            for size in (5, 11, 7)[:n]]


# ------------------------------------------------------------ the tracer ---


def test_scope_is_a_profiler_span_with_rank_and_scalar_args(tmp_path):
    tr = Tracer(rank=3)

    def body():
        with tr.scope("fwd", mb=2, op="fwd", group=(0, 1)):
            tr.record("late", 0.0, 1.0)
            tr.instant("mark")

    spans = profiled(tmp_path, body)
    # record() and instant() carry timestamps after the fact: no span
    assert [s[0] for s in spans] == ["megascan.fwd"]
    assert spans[0][3] == {"rank": 3, "mb": 2, "op": "fwd"}
    # the TraceEvent keeps every arg, the tuple the profiler cannot hold too
    assert [e.name for e in tr.events] == ["late", "mark", "fwd"]
    assert tr.events[-1].args == {"mb": 2, "op": "fwd", "group": (0, 1)}
    assert tr.events[-1].kind == "compute"


def test_no_profiler_or_disabled_tracer_builds_no_span(tmp_path):
    assert isinstance(Tracer(rank=0).annotate("x", step=1), nullcontext)
    off = Tracer(rank=0, enabled=False)

    def body():
        assert isinstance(off.annotate("x"), nullcontext)
        with off.scope("x", step=1):
            pass

    assert profiled(tmp_path, body) == []
    assert off.events == []


def test_host_kind_has_its_own_chrome_thread():
    ev = [TraceEvent("tick", 0, 1.0, 0.5, "host", {"step": 3}),
          TraceEvent("decode", 0, 1.1, 0.3, "compute", {"step": 3})]
    doc = to_chrome(ev)
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert spans["tick"]["tid"] != spans["decode"]["tid"]
    threads = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
               if e["name"] == "thread_name"}
    assert threads[spans["tick"]["tid"]] == "host"
    assert [e.kind for e in from_chrome(doc)] == ["host", "compute"]


# ----------------------------------------------------------- the server ---


def test_serving_tick_spans_nest_in_the_tick(qwen, tmp_path):
    cfg, params = qwen
    clock = itertools.count()
    tracer = Tracer(rank=0, clock=lambda: float(next(clock)))
    srv = MegaServe(cfg, params, SCFG, tracer=tracer,
                    registry=MetricsRegistry())

    def serve():
        for rid, p in enumerate(prompts(cfg, 2)):
            srv.submit(p, 3, arrival=0.0, rid=rid)
        srv.drain()

    serve()                          # compiles every step the run takes
    srv.reset()
    serve()
    plain = list(tracer.events)
    srv.reset()
    spans = profiled(tmp_path, serve)

    names = {s[0] for s in spans}
    assert {"megascan.tick", "megascan.admit", "megascan.upload",
            "megascan.prefill", "megascan.decode", "megascan.pull",
            "megascan.publish"} <= names
    ticks = [s for s in spans if s[0] == "megascan.tick"]
    for name, start, end, _ in spans:
        if name != "megascan.tick":
            assert any(t[1] <= start and end <= t[2] for t in ticks), name
    pre = [s[3] for s in spans if s[0] == "megascan.prefill"]
    assert sorted((st["rid"], st["tokens"]) for st in pre) == [(0, 5), (1, 11)]
    assert all(st["rank"] == 0 for st in pre)
    # the profiler changes nothing the tracer records: same events, args,
    # kinds and (on the counting clock) the same durations
    def shape(evs):
        t0 = evs[0].ts
        return [(e.name, e.kind, e.ts - t0, e.dur, e.args) for e in evs]

    assert shape(tracer.events) == shape(plain)
    assert {e.kind for e in plain if e.name in ("prefill", "decode")} == {
        "compute"}
    assert {e.kind for e in plain
            if e.name in ("tick", "admit", "upload", "pull", "publish")} == {
        "host"}


def test_idle_tick_traces_nothing(qwen):
    cfg, params = qwen
    srv = MegaServe(cfg, params, SCFG)
    out = srv.step()
    assert out == {"admitted": [], "preempted": [], "finished": [],
                   "active": 0, "tokens": 0}
    srv.submit(prompts(cfg, 1)[0], 2, arrival=1e9)   # not due yet
    assert srv.step()["admitted"] == []
    assert srv.trace_events() == []


def test_step_compiled_mid_serving_is_counted_once(qwen):
    cfg, params = qwen
    reg = MetricsRegistry()
    srv = MegaServe(cfg, params, SCFG, registry=reg)
    for p in prompts(cfg, 2):
        srv.submit(p, 3, arrival=0.0)
    srv.drain()
    # before precompile() every step compiles on first use: nothing counted
    assert not any(k.startswith("serve.step_fallbacks") for k in
                   reg.snapshot())
    srv.precompile()
    widths = [k[1] for k in srv._aot_exec if k[0] == "decode"]
    for w in widths:
        del srv._aot_exec[("decode", w)]
    for _ in range(2):               # the second pass reuses the same widths
        for p in prompts(cfg, 2, seed=1):
            srv.submit(p, 3, arrival=0.0)
        srv.drain()
    marks = [e for e in srv.trace_events() if e.name == "step_fallback"]
    assert marks and {e.args["path"] for e in marks} == {"decode"}
    assert all(e.kind == "marker" and e.args["width"] in widths for e in marks)
    n = reg.snapshot()["serve.step_fallbacks.decode"]
    assert n == len(marks) == len({e.args["width"] for e in marks})
    assert "serve.step_fallbacks.prefill" not in reg.snapshot()


def test_serving_steps_carry_their_program_names(qwen):
    cfg, params = qwen
    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=2, block_size=8, num_blocks=17, max_blocks_per_slot=4,
        spec_decode=True, chunked_prefill=True, prefill_path="flash"))
    srv.precompile()
    for kind, key in (("decode", ("decode", 1)), ("verify", ("verify", 1)),
                      ("chunk", ("chunk", 2))):
        assert f"HloModule jit_serve_{kind}" in srv._aot_exec[key].as_text()
    assert "HloModule jit_serve_prefill" in srv._prefill_cache[1].as_text()


# ---------------------------------------------------------- the session ---


@pytest.mark.parametrize("trace_out", ["", "trace.json"])
def test_plugin_hooks_span_and_counter_samples_only_for_a_trace_sink(
        tmp_path, trace_out):
    from repro.app.config import RunConfig
    from repro.app.session import Session

    rc = RunConfig.for_workload(
        "serve", arch="qwen2-0.5b", smoke=True,
        trace_out=str(tmp_path / trace_out) if trace_out else "")
    s = Session(rc)
    s.metrics_registry.counter("serve.tokens").inc(3)
    s.notify_step([], {})            # an idle tick: no span
    with s.tracer.scope("decode", tokens=1):
        pass
    s.notify_step(s.tracer.events[-1:], {})
    names = [e.name for e in s.tracer.events]
    assert names.count("plugins") == 1
    assert s.tracer.events[names.index("plugins")].kind == "host"
    # metric samples become counter events only where the trace export
    # that renders them is asked for
    counters = [e for e in s.tracer.events if e.kind == "counter"]
    assert bool(counters) == bool(trace_out)
    if trace_out:
        assert {e.name for e in counters} == {"serve.tokens"}
    s.finalize()
