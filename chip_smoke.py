#!/usr/bin/env python3
"""Smoke run of the main paths on a TPU, at the full width of qwen2-0.5b.

    python chip_smoke.py              # one chip: the train and serve phases
    python chip_smoke.py --chips 4    # four chips: the dp x tp x pp train path

Everything runs in this one process, because a TPU chip belongs to one
process at a time, and through the code ``python -m repro train|serve`` runs
(``Session``, ``MegaServe``).  Weights are random, made from ``--seed``.

* ``train``: full-width qwen2-0.5b steps on the mesh ``pick_mesh("auto")``
  gives, with finite losses, a nonzero cost-analysis FLOP count, the warm
  step time, tokens/s and the device's peak memory.
* ``serve``: MegaServe continuous batching of mixed-length prompts on the
  paged decode + flash prefill Pallas kernels, checked against a second
  engine forced onto the XLA reference paths: every request finishes and
  every first token agrees.
* ``--chips 4``: the first step of full-width qwen2-0.5b on the
  (stage=2, data=2) and (stage=2, model=2) pipeline meshes and on the plain
  4-device mesh, each compared with the same batch on one device.

Any failed check raises, and the script exits non-zero.  Where JAX finds no
TPU it exits 1 before any work.  Details go to earlier lines; the last line
of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen2-0.5b"
# train: sized so fp32 master + moments, bf16 params and the activations of
# one step fit a 16 GB chip (about 11.9 GiB by the compiler's estimate)
TRAIN_STEPS, SEQ_LEN, GLOBAL_BATCH = 4, 2048, 8
# serve: mixed prompt lengths that hit three prefill buckets, more requests
# than slots so some queue
REQUESTS, PROMPT_LENS, MAX_NEW, SLOTS = 8, (64, 200, 500), 32, 4
# bf16 compute with fp32 losses: the sharded step reduces in another order
# than the one-device step, so the two agree to bf16 rounding, not bitwise
LOSS_RTOL = 1e-2
GRAD_NORM_RTOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def cache_entries(path: str) -> int:
    p = Path(path)
    return sum(1 for f in p.rglob("*") if f.is_file()) if p.is_dir() else 0


class StepClock:
    """Session plugin stamping the host clock after each step has landed
    on the device (``block_until_ready`` on the step's metrics)."""

    name = "step_clock"

    def __init__(self):
        self.stamps: list[float] = []

    def setup(self, session):
        return None

    def wrap_step(self, step_fn):
        return step_fn

    def on_step(self, session, events, metrics):
        import jax

        jax.block_until_ready(metrics)
        self.stamps.append(time.perf_counter())

    def finalize(self, session):
        return {}


def train_session(*, steps: int, seq: int, batch: int, seed: int,
                  modules=("scan", "metrics"), pp: int = 1, dp: int = 1,
                  tp: int = 1, session_cls=None):
    from repro.app.config import RunConfig
    from repro.app.plugins import build_plugins
    from repro.app.session import Session

    rc = RunConfig.for_workload("train", arch=ARCH, seed=seed)
    rc.modules = tuple(modules)
    rc.train.steps = steps
    rc.train.seq_len = seq
    rc.train.global_batch = batch
    rc.train.log_every = 1
    rc.parallel.pp, rc.parallel.dp, rc.parallel.tp = pp, dp, tp
    clock = StepClock()
    session = (session_cls or Session)(
        rc, plugins=build_plugins(rc.modules, rc) + [clock])
    return session, clock


def phase_train(seed: int) -> None:
    import jax

    seq, batch = SEQ_LEN, GLOBAL_BATCH
    session, clock = train_session(
        steps=TRAIN_STEPS, seq=seq, batch=batch, seed=seed)
    t0 = time.perf_counter()
    state, history = session.run()
    n_params = sum(x.size for x in jax.tree.leaves(state.master))
    del state
    losses = [h["loss"] for h in history]
    for h in history:
        log(f"train step {h['step']}: loss {h['loss']!r} "
            f"grad_norm {h['grad_norm']!r}")
    if len(losses) != TRAIN_STEPS or not all(map(math.isfinite, losses)):
        raise AssertionError(f"train losses not all finite: {losses}")
    stamps = [t0] + clock.stamps
    step_s = [b - a for a, b in zip(stamps, stamps[1:])]
    warm = sorted(step_s[1:])[len(step_s[1:]) // 2]
    tokens = seq * batch
    cfg = session.model_cfg
    # forward + backward matmul FLOPs (6 per parameter per token) plus the
    # attention scores and values (12 L H dh S per token); remat not counted
    model_flops = tokens * (
        6 * n_params
        + 12 * cfg.num_layers * cfg.num_heads * cfg.head_dim * seq
    )
    snap = session.metrics_registry.snapshot()
    xla_flops = snap.get("train.step_flops", 0.0)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"train: mesh={session.results.get('parallel', {}).get('mesh') or dict(session.mesh().shape)} "
        f"seq_len={seq} global_batch={batch} params={n_params}")
    log(f"train: step times s {step_s!r}")
    log(f"train: set-up + first step (compiles included) s {step_s[0]!r}")
    log(f"train: warm step s (median of steps 2..) {warm!r}")
    log(f"train: tokens/s {tokens / warm!r}")
    log(f"train: model FLOPs/step (6N + 12 L H dh S per token) "
        f"{float(model_flops)!r}")
    log(f"train: model FLOP/s {model_flops / warm!r}")
    log(f"train: XLA cost-analysis FLOPs/step {xla_flops!r}")
    log(f"train: peak_bytes_in_use {peak}")
    if not xla_flops > 0:
        raise AssertionError("train step cost analysis gave no FLOP count")


def serve_prompts(vocab: int, seed: int, n: int, lens):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, size=lens[i % len(lens)]).tolist()
            for i in range(n)]


def drain(srv, prompts, max_new):
    rids = [srv.submit(p, max_new, arrival=0.0) for p in prompts]
    t0 = time.perf_counter()
    outs = srv.drain()
    wall = time.perf_counter() - t0
    return [outs[r] for r in rids], [srv.sched.requests[r] for r in rids], wall


def lowered_text(srv, n_slots: int, width: int, n_blk: int) -> tuple[str, str]:
    """StableHLO of one decode step and one prefill bucket (lowering only)."""
    import jax
    import jax.numpy as jnp

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    pa, pool = srv._avatar(srv.params), srv._avatar(srv.pool)
    bs = srv.serve_cfg.block_size
    dec = srv._decode_jit.lower(
        pa, pool, i32(n_slots, width), i32(n_slots), i32(n_slots)).as_text()
    pre = srv._build_prefill_jit(n_blk).lower(
        pa, i32(1, n_blk * bs), i32(), pool, i32(), i32(n_blk)).as_text()
    return dec, pre


def phase_serve(seed: int) -> None:
    from dataclasses import replace

    import jax

    from repro.app.config import RunConfig
    from repro.app.session import Session
    from repro.models import get_model
    from repro.serve import MegaServe, ServeConfig
    from repro.serve.paged_cache import blocks_for

    session = Session(RunConfig.for_workload("serve", arch=ARCH, seed=seed))
    cfg = session.model_cfg
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(seed))
    prompts = serve_prompts(cfg.vocab_size, seed, REQUESTS, PROMPT_LENS)
    bs = 16
    worst = blocks_for(max(PROMPT_LENS) + MAX_NEW, bs)
    scfg = ServeConfig(num_slots=SLOTS, block_size=bs,
                       num_blocks=SLOTS * worst + 1,
                       max_blocks_per_slot=worst)

    srv = MegaServe.from_session(session, params, scfg)
    if (srv.decode_path, srv.prefill_path) != ("paged", "flash"):
        raise AssertionError(
            f"serving resolved to decode_path={srv.decode_path} "
            f"prefill_path={srv.prefill_path}, not paged + flash")
    dec, pre = lowered_text(srv, SLOTS, worst, worst)
    if "tpu_custom_call" not in dec or "tpu_custom_call" not in pre:
        raise AssertionError("paged decode / flash prefill did not lower "
                             "to the Pallas TPU kernels")
    cold_out, _, cold_wall = drain(srv, prompts, MAX_NEW)
    srv.reset()
    out, reqs, warm_wall = drain(srv, prompts, MAX_NEW)
    if out != cold_out:
        raise AssertionError("warm serve pass differs from the cold one")

    ref = MegaServe.from_session(
        session, params,
        replace(scfg, paged_attn_impl="xla", prefill_path="dense"))
    rdec, rpre = lowered_text(ref, SLOTS, worst, worst)
    if "tpu_custom_call" in rdec or "tpu_custom_call" in rpre:
        raise AssertionError("the XLA reference engine lowered to a kernel")
    ref_out, _, ref_wall = drain(ref, prompts, MAX_NEW)

    done = [len(o) == MAX_NEW for o in out]
    if not all(done):
        raise AssertionError(f"unfinished requests: {done}")
    first_ok = [a[0] == b[0] for a, b in zip(out, ref_out)]
    same = sum(x == y for a, b in zip(out, ref_out) for x, y in zip(a, b))
    total = sum(len(a) for a in out)
    ttft = [r.ttft for r in reqs]
    gaps = [(r.t_finished - r.t_first_token) / (len(r.generated) - 1)
            for r in reqs]
    log(f"serve: requests={len(prompts)} prompt_lens={[len(p) for p in prompts]} "
        f"max_new={MAX_NEW} slots={SLOTS} "
        f"decode_path={srv.decode_path} prefill_path={srv.prefill_path} "
        f"paged_attn_impl={scfg.paged_attn_impl} (Pallas on {jax.default_backend()})")
    log(f"serve: drain s cold (compiles included) {cold_wall!r} "
        f"warm {warm_wall!r}; reference engine cold {ref_wall!r}")
    log(f"serve: warm TTFT s {ttft!r}")
    log(f"serve: warm inter-token gap s {gaps!r}")
    log(f"serve: warm generated tokens/s {total / warm_wall!r}")
    log(f"serve: first tokens identical to the XLA reference: "
        f"{sum(first_ok)}/{len(first_ok)}")
    log(f"serve: greedy tokens identical to the XLA reference: "
        f"{same}/{total} = {same / total!r}")
    if not all(first_ok):
        raise AssertionError(f"first tokens differ from the XLA reference: "
                             f"{[(a[0], b[0]) for a, b in zip(out, ref_out)]}")


def phase_four_chips(seed: int) -> None:
    import jax

    from repro.app.session import Session
    from repro.launch.mesh import auto_mesh

    class OneDevice(Session):
        def mesh(self):
            return auto_mesh((1, 1), ("data", "model"),
                             devices=jax.devices()[:1])

    def first_step(**kw):
        session, _ = train_session(
            steps=1, seq=SEQ_LEN, batch=GLOBAL_BATCH, seed=seed, modules=(),
            **kw)
        t0 = time.perf_counter()
        state, history = session.run()
        del state
        gc.collect()
        h = history[0]
        mesh = (session.results.get("parallel", {}).get("mesh")
                or dict(session.mesh().shape))
        return h["loss"], h["grad_norm"], mesh, time.perf_counter() - t0

    ref_loss, ref_gn, _, ref_s = first_step(session_cls=OneDevice)
    log(f"4chips: one device: loss {ref_loss!r} grad_norm {ref_gn!r} "
        f"({ref_s!r} s with compile)")
    bad = []
    for label, kw in (("pp2 x dp2", dict(pp=2, dp=2)),
                      ("pp2 x tp2", dict(pp=2, tp=2)),
                      ("auto 4-device", {})):
        loss, gn, mesh, s = first_step(**kw)
        dl = abs(loss - ref_loss) / abs(ref_loss)
        dg = abs(gn - ref_gn) / abs(ref_gn)
        ok = dl <= LOSS_RTOL and dg <= GRAD_NORM_RTOL
        log(f"4chips: {label} mesh={mesh}: loss {loss!r} (rel {dl!r}) "
            f"grad_norm {gn!r} (rel {dg!r}) {'ok' if ok else 'MISMATCH'} "
            f"({s!r} s with compile)")
        if not ok:
            bad.append(label)
    log(f"4chips: tolerance loss rel <= {LOSS_RTOL} and grad_norm rel <= "
        f"{GRAD_NORM_RTOL} against one device (bf16 compute)")
    if bad:
        raise AssertionError(f"meshes disagree with one device: {bad}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, data and prompts")
    args = ap.parse_args(argv)

    from repro.core.compile_cache import use_jax_cache

    cache_dir = use_jax_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devs[0].platform!r}); this script runs on the chip only")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {len(devs)}")
    import logging

    logging.basicConfig(level=logging.WARNING, format="%(name)s: %(message)s")
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    log(f"compile cache: {cache_dir} entries before {cache_entries(cache_dir)}")
    phases = ([("4chips", phase_four_chips)] if args.chips == 4
              else [("train", phase_train), ("serve", phase_serve)])
    for name, fn in phases:
        t0 = time.perf_counter()
        fn(args.seed)
        gc.collect()
        log(f"phase {name}: {time.perf_counter() - t0!r} s")
    log(f"compile cache: {cache_dir} entries after {cache_entries(cache_dir)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
