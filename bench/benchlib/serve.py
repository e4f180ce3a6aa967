"""The serving runner: MegaServe under open-loop traffic, then the check.

Set-up builds what ``python -m repro serve --continuous`` builds (a
``Session`` with its default modules, ``MegaServe.from_session``,
``precompile()``), with the benchmark's weights, and warms every prefill
bucket and table width the traffic reaches.  The window then offers the
cell's requests at their due times, stepping the server as its own drain loop
does, until ``seconds`` have passed; the requests due in the window are then
served to the end (at most ``LATE_S`` past the close).  Latencies count from
the due time, so a tick that stalls delays every request behind it.

After the window the program's state is freed and the plain reference
judges a sample of the finished requests (see ``check``).
"""

from __future__ import annotations

import gc
import math
import sys
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from benchlib import flops as F
from benchlib.traffic import Req, quantile_lengths, serve_requests

LATE_S = 60.0          # how long past the close a due request is waited for


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def model_config(config: dict):
    """The program's ``ModelConfig`` for a config file: its registered arch
    with every size the file states."""
    from repro.configs import get_config

    m = config["config"]
    run = config["as_run"]
    heads = int(m["num_attention_heads"])
    over = dict(
        num_layers=int(m["num_hidden_layers"]),
        d_model=int(m["hidden_size"]),
        num_heads=heads,
        num_kv_heads=int(m["num_key_value_heads"]),
        head_dim=int(m.get("head_dim", int(m["hidden_size"]) // heads)),
        d_ff=int(m["intermediate_size"]),
        vocab_size=int(m["vocab_size"]),
        rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        qkv_bias=bool(run.get("qkv_bias", False)),
        vocab_pad_to=int(run.get("vocab_pad_to", 256)),
    )
    for k in ("scale_emb", "scale_depth", "dim_model_base"):
        if k in m:
            over[k] = type(getattr(get_config(run["arch"]), k))(m[k])
    return get_config(run["arch"]).replace(**over)


def step_kind(srv, args) -> str:
    """Which jitted step a ``wrap_step`` call is, told by where the server's
    own KV pool sits among its arguments: ``decode(params, pool, tables,
    tokens, pos)`` or ``prefill(params, tokens, n_real, pool, slot, phys)``.
    The cell's server runs neither speculative verification nor chunked
    prefill; any other call raises rather than be counted as the wrong kind."""
    if len(args) == 5 and args[1] is srv.pool:
        return "decode"
    if len(args) == 6 and args[3] is srv.pool:
        return "prefill"
    raise RuntimeError(
        f"unknown serving step: {len(args)} arguments, the pool at "
        f"{[i for i, a in enumerate(args) if a is srv.pool]}")


class Spans:
    """Session plugin: a profiler span around every jitted prefill and decode
    call (traced runs only), and a record of each call's real work while the
    profiler runs."""

    name = "bench_spans"

    def __init__(self):
        self.srv = None
        self.recording = False
        self.decode_kv: list[list[int]] = []   # live kv length per slot, per call
        self.prefill_n: list[int] = []          # real prompt tokens, per call

    def setup(self, session):
        return None

    def on_step(self, session, events, metrics):
        return None

    def finalize(self, session):
        return {}

    def wrap_step(self, fn):
        import jax

        def call(*args):
            kind = step_kind(self.srv, args)
            if self.recording:
                if kind == "decode":
                    s = self.srv.sched
                    self.decode_kv.append([s.pos[i] + 1 for i in s.active_slots()])
                else:
                    self.prefill_n.append(int(args[2]))
            with jax.profiler.TraceAnnotation(kind):
                return fn(*args)

        return call


@dataclass
class Window:
    seconds: float
    ticks: list[tuple[float, int]] = field(default_factory=list)  # (t_end, tokens)
    trace_t0: float | None = None
    trace_t1: float | None = None
    compiles: int = 0
    traces: int = 0
    t_done: float = 0.0   # when the due requests were served (or given up)


def _buckets(traffic: dict, bs: int, max_w: int) -> list[int]:
    """Prompt lengths that reach every prefill bucket the traffic can use."""
    from repro.serve.paged_cache import blocks_for, pow2_bucket

    lens = quantile_lengths(traffic["prompt_len"], 4096)
    want = sorted({min(pow2_bucket(blocks_for(n, bs)), max_w) for n in lens})
    return [max(1, w * bs - 1) for w in want]


def build(cell, seed: int, trace: bool):
    """Set-up: weights, server, compiled and warmed steps."""
    import jax

    from repro.app.config import RunConfig
    from repro.app.plugins import build_plugins
    from repro.app.session import Session
    from repro.serve import MegaServe
    from repro.serve.paged_cache import blocks_for
    from repro.serve.scheduler import ServeConfig

    from benchlib.weights import make_weights

    config, traffic = cell.config, cell.traffic
    dense = F.Dense.from_config(config)
    cfg = model_config(config)
    rc = RunConfig.for_workload("serve", arch=config["as_run"]["arch"],
                                seed=seed)
    spans = Spans() if trace else None
    plugins = build_plugins(rc.modules, rc) + ([spans] if spans else [])
    session = Session(rc, plugins=plugins, model_cfg=cfg)
    params = make_weights(dense, seed, config["as_run"]["serve_dtype"])
    srv_cfg = traffic["server"]
    bs = int(srv_cfg["block_size"])
    worst = blocks_for(int(traffic["prompt_len"]["max"])
                       + int(traffic["output_len"]["max"]), bs)
    scfg = ServeConfig(num_slots=int(srv_cfg["slots"]), block_size=bs,
                       num_blocks=int(config["kv_pool_blocks"]),
                       max_blocks_per_slot=worst)
    srv = MegaServe.from_session(session, params, scfg, clock=time.perf_counter)
    if spans is not None:
        spans.srv = srv
    want = tuple(srv_cfg.get("paths", ()))
    if want and (srv.decode_path, srv.prefill_path) != tuple(want):
        raise RuntimeError(
            f"serving resolved to decode={srv.decode_path} "
            f"prefill={srv.prefill_path}, the cell asks for {want}")
    t0 = time.perf_counter()
    pre = srv.precompile()
    log(f"serve: precompile {pre['total']} executables in "
        f"{time.perf_counter() - t0:.2f} s")
    # warm every prefill bucket (and the eager ops around it) and the
    # widest decode tables, one short request at a time
    rng = np.random.default_rng(0)
    vocab = dense.vocab
    for n in _buckets(traffic, bs, worst):
        srv.submit(rng.integers(2, vocab, size=n).tolist(), 2, arrival=0.0)
        srv.drain()
    # the longest prompt and one decode past it: the widest table
    longest = int(traffic["prompt_len"]["max"])
    srv.submit(rng.integers(2, vocab, size=longest).tolist(), 2, arrival=0.0)
    srv.drain()
    jax.block_until_ready(srv.pool)
    return session, srv, dense, spans


def trace_options():
    """Device ops and ``TraceAnnotation`` spans only: the Python tracer
    would record every host call and slow the tick it is measuring."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def run_window(session, srv, reqs: list[Req], seconds: float, *,
               trace_dir: str | None = None, trace_from: float = 0.0,
               trace_len: float = 0.0, spans: Spans | None = None,
               late_s: float = LATE_S) -> Window:
    """Offer ``reqs`` at their due times for ``seconds``, then serve what is
    due to the end, waiting at most ``late_s`` past the close.  With
    ``trace_dir`` the profiler records the ticks from ``trace_from`` to
    ``trace_from + trace_len`` seconds into the window."""
    import jax

    from benchlib.compiles import CompileCounter

    # the server's clock restarts at reset() from the same perf_counter
    # (``build`` hands it over), so this one runs a few microseconds ahead
    t_open = time.perf_counter()
    srv.reset()
    clock = lambda: time.perf_counter() - t_open  # noqa: E731
    for r in reqs:
        srv.submit(r.prompt, r.max_new, arrival=r.arrival, rid=r.rid)
    win = Window(seconds=seconds)
    tracing = False
    counter = CompileCounter()
    with counter:
        while True:
            now = clock()
            if trace_dir is not None:
                if not tracing and win.trace_t0 is None and now >= trace_from:
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=trace_options())
                    spans.recording = tracing = True
                    win.trace_t0 = clock()
                elif tracing and now >= trace_from + trace_len:
                    # the window ends before the profiler's own stop work
                    win.trace_t1 = clock()
                    jax.profiler.stop_trace()
                    spans.recording = tracing = False
            if srv.sched.all_done or now > seconds + late_s:
                break
            n_ev = len(srv.tracer.events)
            if tracing:
                with jax.profiler.TraceAnnotation("MegaServe.step"):
                    out = srv.step()
            else:
                out = srv.step()
            session.notify_step(srv.tracer.events[n_ev:], out)
            win.ticks.append((clock(), out["tokens"]))
            if not (out["admitted"] or out["active"]):
                nxt = srv.sched.next_arrival()
                if nxt is not None:
                    time.sleep(max(0.0, min(nxt - clock(), 1e-3)))
        if tracing:
            win.trace_t1 = clock()
            jax.profiler.stop_trace()
            spans.recording = False
    win.t_done = clock()
    win.compiles = counter.count
    win.traces = counter.traces
    return win


def pct(values: list[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it."""
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def end_to_end(srv, win: Window, t_close_wait: float) -> tuple[dict, int, int]:
    """(metrics, attempted, failed) over the requests due in the window.  A
    request not finished counts with the time it was waited for (its first
    token, where it had one)."""
    reqs = list(srv.sched.requests.values())
    attempted = len(reqs)
    ttft, tpot, failed = [], [], 0
    for r in reqs:
        if r.t_finished is None or len(r.generated) < r.max_new:
            failed += 1
            first = r.t_first_token if r.t_first_token is not None else t_close_wait
            ttft.append(first - r.arrival)
            tpot.append(t_close_wait - r.arrival)
            continue
        ttft.append(r.t_first_token - r.arrival)
        tpot.append((r.t_finished - r.t_first_token)
                    / max(len(r.generated) - 1, 1))
    tokens = sum(n for t, n in win.ticks if t <= win.seconds)
    metrics = {
        "ttft_p95_ms": pct(ttft, 95) * 1e3,
        "tpot_p95_ms": pct(tpot, 95) * 1e3,
        "serve_tokens_per_s": tokens / win.seconds,
    }
    return metrics, attempted, failed


def request_records(srv) -> list[dict]:
    out = []
    for r in srv.sched.requests.values():
        out.append({
            "rid": r.rid, "arrival": r.arrival, "t_admitted": r.t_admitted,
            "t_first_token": r.t_first_token, "t_finished": r.t_finished,
            "prompt_len": r.prompt_len, "generated": len(r.generated),
            "max_new": r.max_new, "preemptions": r.n_preemptions,
        })
    return out


def sample(finished: list, seed: int, n: int) -> list[int]:
    """Indices of ``n`` finished requests: the longest (prompt and served
    tokens together), and the others drawn from the seed."""
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 7])
    return [order[0]] + [rest[i] for i in rng.permutation(len(rest))[: n - 1]]


def check(weights, dense: F.Dense, finished: list[tuple[list[int], list[int]]],
          seed: int, n_sample: int) -> dict:
    """Judge a sample of finished requests by the plain float32 reference:
    how far each served token's logit lies below the reference's best at its
    position.  Greedy decoding would put the best there, so a sound run loses
    only where rounding flips a near tie.  The mean over the sampled tokens
    is compared (it counts how often and by how much); the widest gap is
    logged beside it."""
    from benchlib.reference import served_gaps

    gaps = [served_gaps(weights, dense, *finished[i])
            for i in sample(finished, seed, n_sample)]
    every = np.concatenate(gaps)
    return {"mean_logit_gap": float(every.mean()),
            "widest_logit_gap": float(every.max()),
            "sampled_requests": len(gaps), "sampled_tokens": int(every.size)}


def _hist_sums(reg) -> dict[str, tuple[float, int]]:
    out = {}
    if reg is None:
        return out
    for k, v in reg.snapshot().items():
        if isinstance(v, dict) and "sum" in v:
            out[k] = (v["sum"], v["count"])
    return out


def run_cell(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
             devs, peak, trace_root: str) -> dict:
    """One run of a serving cell; returns the result's fields."""
    import jax

    from benchlib import xplane
    from benchlib.device import memory_peak_bytes

    session, srv, dense, spans = build(cell, seed, trace)
    reqs = serve_requests(cell.traffic, seed, seconds, dense.vocab)
    reg = session.metrics_registry
    before = _hist_sums(reg)
    trace_len = min(5.0, seconds / 4)
    trace_from = seconds / 2 - trace_len / 2
    setup_s = time.perf_counter() - t_start
    log(f"serve: set-up {setup_s:.2f} s; {len(reqs)} requests due in "
        f"{seconds} s")
    win = run_window(session, srv, reqs, seconds,
                     trace_dir=trace_root if trace else None,
                     trace_from=trace_from, trace_len=trace_len, spans=spans,
                     late_s=float(cell.traffic["check"].get("late_s", LATE_S)))
    t_wait = win.t_done
    e2e, attempted, failed = end_to_end(srv, win, t_wait)
    log(f"serve: window {seconds} s, served to the end at {t_wait:.2f} s; "
        f"{win.compiles} compiles and {win.traces} traces inside")
    records = request_records(srv)
    after = _hist_sums(reg)
    spent = {k: (v[0] - before.get(k, (0.0, 0))[0],
                 v[1] - before.get(k, (0.0, 0))[1]) for k, v in after.items()}
    prompts = {r.rid: r.prompt for r in reqs}
    finished = [(prompts[r.rid], list(r.generated))
                for r in srv.sched.requests.values()
                if r.t_finished is not None and len(r.generated) == r.max_new]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak_bytes(devs)}
    weights = srv.params
    # free the program's state before the reference runs
    srv.pool = None
    del srv, session
    gc.collect()

    out: dict[str, Any] = {"attempted": attempted, "failed": failed,
                           "device": device}
    if trace:
        tr = xplane.load(xplane.find_xplane(trace_root),
                         host_names={"MegaServe.step", "prefill", "decode"})
        window_s = win.trace_t1 - win.trace_t0
        busy = xplane.busy_s(tr)
        t0, t1 = xplane.extent(tr)
        device["busy_s"] = busy
        device["window_s"] = window_s
        rec = {
            "model": dense, "peak": peak, "requests": records,
            "hist": spent, "trace": tr, "window_s": window_s,
            "decode_kv": spans.decode_kv, "prefill_n": spans.prefill_n,
        }
        out["per_layer"] = rec
        out["breakdown"] = {"device_ops": xplane.top_ops(tr),
                            "idle_gaps": xplane.idle_gaps(tr, t0, t1)}
    else:
        out["end_to_end"] = {**e2e, "setup_s": setup_s}
    limit = float(cell.config["correct"]["mean_logit_gap"])
    n_sample = int(cell.traffic["check"]["sample_requests"])
    got = check(weights, dense, finished, seed, n_sample) if finished else {
        "mean_logit_gap": float("nan"), "widest_logit_gap": float("nan"),
        "sampled_requests": 0, "sampled_tokens": 0}
    jax.block_until_ready(weights)
    gap = got["mean_logit_gap"]
    log(f"serve: reference judged {got['sampled_requests']} requests, "
        f"{got['sampled_tokens']} served tokens; widest logit gap "
        f"{got['widest_logit_gap']!r}")
    out["checks"] = {
        "mean_logit_gap": {"value": gap, "limit": limit},
        "unfinished_requests": {"value": failed, "limit": 0},
    }
    out["correct"] = bool(failed == 0 and finished and gap <= limit)
    return out
