"""The serving runner of latent-attention MoE models (DeepSeek-V2) at one
chip's share of their experts.

The traffic file's ``kind`` ``serve_latent_moe`` picks it.  It serves as
``benchlib.serve`` does, and takes from it, unchanged, the window
(``run_window``), the traffic's warm-up buckets, the end-to-end numbers
(``end_to_end``), the request records and the sample the check judges.  What
is its own: the mapping from the configuration file to the program
(``LatentMoE.from_config``, which refuses a key it does not map), the
weights (the program's MoE tree, made from the seed), the float32 reference
and its control (``benchlib.latent_moe_reference``), and the counts behind
the per-layer metrics (``benchlib.latent_moe_model``).  A traced run also
records, for each decode call, the live kv length of every slot and the
routing picks per held expert in every MoE layer (the step's own counts,
read after the call).
"""

from __future__ import annotations

import gc
import math
import time
from typing import Any

import numpy as np

from benchlib import serve as S
from benchlib.latent_moe_model import LatentMoE
from benchlib.traffic import serve_requests


def model_config(m: LatentMoE, arch: str):
    """The program's ``ModelConfig``: its registered arch with every size and
    setting the file states."""
    from repro.configs import MLAConfig, MoEConfig, RopeScaling, get_config

    base = get_config(arch)
    return base.replace(
        num_layers=m.n_layers, d_model=m.d_model, num_heads=m.n_heads,
        num_kv_heads=m.n_heads, head_dim=m.v_head, d_ff=m.d_ff,
        vocab_size=m.vocab, vocab_pad_to=m.vocab_pad_to,
        tie_embeddings=False, norm_eps=m.norm_eps, rope_theta=m.rope_theta,
        use_mla=True,
        mla=MLAConfig(kv_lora_rank=m.kv_lora_rank, q_lora_rank=0,
                      qk_nope_head_dim=m.qk_nope, qk_rope_head_dim=m.qk_rope,
                      v_head_dim=m.v_head),
        moe=MoEConfig(
            num_experts=m.n_experts, num_shared_experts=m.n_shared,
            top_k=m.top_k, expert_d_ff=m.expert_d_ff,
            first_k_dense=m.first_k_dense, norm_topk_prob=m.norm_topk_prob,
            routed_scaling_factor=m.routed_scaling_factor,
            experts_held=m.experts_held, expert_offset=m.expert_offset),
        rope_scaling=RopeScaling(
            factor=m.yarn_factor,
            original_max_position_embeddings=m.yarn_original,
            beta_fast=m.yarn_beta_fast, beta_slow=m.yarn_beta_slow,
            mscale=m.yarn_mscale, mscale_all_dim=m.yarn_mscale_all_dim),
    )


def _leaves(m: LatentMoE) -> list[tuple[tuple[str, ...], tuple[int, ...], str, float]]:
    """(path, shape, kind, scale) of every leaf of the program's MoE tree."""
    D, H, r, dr = m.d_model, m.n_heads, m.kv_lora_rank, m.qk_rope
    dq = m.qk_nope + dr
    proj = 1.0 / math.sqrt(2 * m.n_layers)
    fan = lambda n: 1.0 / math.sqrt(n)  # noqa: E731
    out = [
        (("embedding",), (m.vocab_padded, D), "normal", fan(D)),
        (("unembed",), (D, m.vocab_padded), "normal", fan(D)),
        (("final_norm", "scale"), (D,), "scale", 0.1),
    ]
    segs = [("seg0", m.first_k_dense, False), ("seg1", m.n_moe_layers, True)]
    for seg, n, moe in segs:
        pre = (seg, "b0")
        out += [
            (pre + ("ln1", "scale"), (n, D), "scale", 0.1),
            (pre + ("ln2", "scale"), (n, D), "scale", 0.1),
            (pre + ("attn", "wq"), (n, D, H, dq), "normal", fan(D)),
            (pre + ("attn", "wdkv"), (n, D, r), "normal", fan(D)),
            (pre + ("attn", "wkr"), (n, D, dr), "normal", fan(D)),
            (pre + ("attn", "kv_norm"), (n, r), "scale", 0.1),
            (pre + ("attn", "wuk"), (n, r, H, m.qk_nope), "normal", fan(r)),
            (pre + ("attn", "wuv"), (n, r, H, m.v_head), "normal", fan(r)),
            (pre + ("attn", "wo"), (n, H, m.v_head, D), "normal",
             proj * fan(H * m.v_head)),
        ]
        if not moe:
            F = m.d_ff
            out += [
                (pre + ("mlp", "w_gate"), (n, D, F), "normal", fan(D)),
                (pre + ("mlp", "w_up"), (n, D, F), "normal", fan(D)),
                (pre + ("mlp", "w_down"), (n, F, D), "normal", proj * fan(F)),
            ]
            continue
        E, F, Fs = m.experts_held, m.expert_d_ff, m.n_shared * m.expert_d_ff
        out += [
            (pre + ("mlp", "router"), (n, D, m.n_experts), "normal", fan(D)),
            (pre + ("mlp", "w_gate"), (n, E, D, F), "normal", fan(D)),
            (pre + ("mlp", "w_up"), (n, E, D, F), "normal", fan(D)),
            (pre + ("mlp", "w_down"), (n, E, F, D), "normal", proj * fan(F)),
            (pre + ("mlp", "shared", "w_gate"), (n, D, Fs), "normal", fan(D)),
            (pre + ("mlp", "shared", "w_up"), (n, D, Fs), "normal", fan(D)),
            (pre + ("mlp", "shared", "w_down"), (n, Fs, D), "normal",
             proj * fan(Fs)),
        ]
    return out


def make_weights(m: LatentMoE, seed: int, dtype: str):
    """The weight tree, made on the default device one leaf at a time (so no
    float32 copy of a large leaf waits beside the others): normal with the
    usual 1/sqrt(fan in) scale, output projections by 1/sqrt(2 L), norm
    scales around 1; padded vocabulary rows zero."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from benchlib.weights import seed_key

    dt = jnp.dtype(dtype)

    @partial(jax.jit, static_argnames=("shape", "kind", "scale", "pad_rows",
                                       "pad_cols"))
    def leaf(key, *, shape, kind, scale, pad_rows, pad_cols):
        x = jax.random.normal(key, shape, jnp.float32) * scale
        if kind == "scale":
            x = 1.0 + x
        if pad_rows:
            x = x.at[m.vocab:].set(0.0)
        if pad_cols:
            x = x.at[:, m.vocab:].set(0.0)
        return x.astype(dt)

    spec = _leaves(m)
    keys = jax.random.split(seed_key(seed), len(spec))
    padded = m.vocab_padded != m.vocab
    tree: dict = {}
    for k, (path, shape, kind, scale) in zip(keys, spec):
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf(k, shape=shape, kind=kind, scale=scale,
                              pad_rows=padded and path == ("embedding",),
                              pad_cols=padded and path == ("unembed",))
    return tree


class Spans(S.Spans):
    """``benchlib.serve.Spans``, which also reads each traced decode call's
    routing picks per held expert (``moe_held_picks`` in the step's
    captures)."""

    def __init__(self):
        super().__init__()
        self.decode_picks: list[np.ndarray] = []   # [n_moe_layers, held]

    def wrap_step(self, fn):
        inner = super().wrap_step(fn)

        def call(*args):
            recording = self.recording and S.step_kind(self.srv, args) == "decode"
            out = inner(*args)
            picks = out[2].get("moe_held_picks") if recording else None
            if picks is not None:
                self.decode_picks.append(np.asarray(picks))
            return out

        return call


def build(cell, seed: int, trace: bool):
    """Set-up: configuration, server, weights, compiled and warmed steps.
    The configuration is built first, so a program without the latent paged
    cache or the chip-share expert layer fails here, at once."""
    import jax

    from repro.app.config import RunConfig
    from repro.app.plugins import build_plugins
    from repro.app.session import Session
    from repro.serve import MegaServe
    from repro.serve.paged_cache import blocks_for
    from repro.serve.scheduler import ServeConfig

    config, traffic = cell.config, cell.traffic
    m = LatentMoE.from_config(config)
    arch = config["as_run"]["arch"]
    cfg = model_config(m, arch)
    rc = RunConfig.for_workload("serve", arch=arch, seed=seed)
    spans = Spans() if trace else None
    plugins = build_plugins(rc.modules, rc) + ([spans] if spans else [])
    session = Session(rc, plugins=plugins, model_cfg=cfg)
    params = make_weights(m, seed, config["as_run"]["serve_dtype"])
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
    if want and (srv.decode_path, srv.prefill_path) != want:
        raise RuntimeError(
            f"serving resolved to decode={srv.decode_path} "
            f"prefill={srv.prefill_path}, the cell asks for {want}")
    t0 = time.perf_counter()
    pre = srv.precompile()
    S.log(f"serve: precompile {pre['total']} executables in "
          f"{time.perf_counter() - t0:.2f} s")
    rng = np.random.default_rng(0)
    for n in S._buckets(traffic, bs, worst):
        srv.submit(rng.integers(2, m.vocab, size=n).tolist(), 2, arrival=0.0)
        srv.drain()
    longest = int(traffic["prompt_len"]["max"])
    srv.submit(rng.integers(2, m.vocab, size=longest).tolist(), 2, arrival=0.0)
    srv.drain()
    jax.block_until_ready(srv.pool)
    return session, srv, m, spans


def check(weights, m: LatentMoE, finished, seed: int, n_sample: int) -> dict:
    """As ``benchlib.serve.check``, against this model's float32 reference."""
    from benchlib.latent_moe_reference import served_gaps

    gaps = [served_gaps(weights, m, *finished[i])
            for i in S.sample(finished, seed, n_sample)]
    every = np.concatenate(gaps)
    return {"mean_logit_gap": float(every.mean()),
            "widest_logit_gap": float(every.max()),
            "sampled_requests": len(gaps), "sampled_tokens": int(every.size)}


def run_cell(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
             devs, peak, trace_root: str) -> dict:
    """One run of the cell; returns the result's fields (as
    ``benchlib.serve.run_cell``)."""
    import jax

    from benchlib import megascan, xplane
    from benchlib.device import memory_peak_bytes

    session, srv, m, spans = build(cell, seed, trace)
    reqs = serve_requests(cell.traffic, seed, seconds, m.vocab)
    reg = session.metrics_registry
    before = S._hist_sums(reg)
    trace_len = min(5.0, seconds / 4)
    trace_from = seconds / 2 - trace_len / 2
    setup_s = time.perf_counter() - t_start
    S.log(f"serve: set-up {setup_s:.2f} s; {len(reqs)} requests due in "
          f"{seconds} s")
    win = S.run_window(session, srv, reqs, seconds,
                       trace_dir=trace_root if trace else None,
                       trace_from=trace_from, trace_len=trace_len, spans=spans,
                       late_s=float(cell.traffic["check"].get("late_s",
                                                              S.LATE_S)))
    e2e, attempted, failed = S.end_to_end(srv, win, win.t_done)
    S.log(f"serve: window {seconds} s, served to the end at {win.t_done:.2f} "
          f"s; {win.compiles} compiles and {win.traces} traces inside")
    records = S.request_records(srv)
    after = S._hist_sums(reg)
    spent = {k: (v[0] - before.get(k, (0.0, 0))[0],
                 v[1] - before.get(k, (0.0, 0))[1]) for k, v in after.items()}
    prompts = {r.rid: r.prompt for r in reqs}
    finished = [(prompts[r.rid], list(r.generated))
                for r in srv.sched.requests.values()
                if r.t_finished is not None and len(r.generated) == r.max_new]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak_bytes(devs)}
    weights = srv.params
    srv.pool = None          # free the program's state before the reference
    del srv, session
    gc.collect()

    out: dict[str, Any] = {"attempted": attempted, "failed": failed,
                           "device": device}
    if trace:
        path = xplane.find_xplane(trace_root)
        tr = xplane.load(path, host_names={"MegaServe.step", "prefill", "decode"})
        window_s = win.trace_t1 - win.trace_t0
        t0, t1 = xplane.extent(tr)
        device["busy_s"] = xplane.busy_s(tr)
        device["window_s"] = window_s
        out["per_layer"] = {
            "model": m, "peak": peak, "requests": records, "hist": spent,
            "trace": tr, "window_s": window_s, "decode_kv": spans.decode_kv,
            "prefill_n": spans.prefill_n, "decode_picks": spans.decode_picks,
            "megascan": megascan.load(path),
        }
        out["breakdown"] = {"device_ops": xplane.top_ops(tr),
                            "idle_gaps": xplane.idle_gaps(tr, t0, t1)}
    else:
        out["end_to_end"] = {**e2e, "setup_s": setup_s}
    limit = float(cell.config["correct"]["mean_logit_gap"])
    n_sample = int(cell.traffic["check"]["sample_requests"])
    got = check(weights, m, finished, seed, n_sample) if finished else {
        "mean_logit_gap": float("nan"), "widest_logit_gap": float("nan"),
        "sampled_requests": 0, "sampled_tokens": 0}
    jax.block_until_ready(weights)
    gap = got["mean_logit_gap"]
    S.log(f"serve: reference judged {got['sampled_requests']} requests, "
          f"{got['sampled_tokens']} served tokens; widest logit gap "
          f"{got['widest_logit_gap']!r}")
    out["checks"] = {
        "mean_logit_gap": {"value": gap, "limit": limit},
        "unfinished_requests": {"value": failed, "limit": 0},
    }
    out["correct"] = bool(failed == 0 and finished and gap <= limit)
    return out
