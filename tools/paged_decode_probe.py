#!/usr/bin/env python3
"""Time the paged decode attention kernel alone, at a served model's widths,
on live lengths drawn from a chat mix.

    python3 tools/paged_decode_probe.py                    # qwen2-0.5b widths
    python3 tools/paged_decode_probe.py --active 16,40 --draws 3 --reps 20

Builds a layer-stacked bfloat16 KV pool on the device (``--layers`` x
``--pool-blocks`` blocks of ``--block-size`` rows) and, per draw, gives
``--active`` of the ``--slots`` slots a live length of a lognormal prompt
plus a uniform share of a lognormal answer (the chat mix's shapes, clipped
to fit the pool); the other slots idle at one row on the null block, as the
server's idle slots do.  The block table is as wide as the server would
slice it (the next power of two over the longest live slot).  One jitted
program calls ``paged_attention_pallas`` once per layer, as the decode step
does; it is timed over ``--reps`` calls after a warm-up.

One JSON line per (active, draw): µs per kernel call, live-row GB/s (the K
and V rows of every slot's live length over the call time), the chunks the
kernel walks per call (where the kernel has a live-block walk), and the
largest gap to the XLA reference on the same inputs.  Runs on the CPU in
interpret mode with ``--interpret`` (a smoke test; its times mean nothing).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402


def live_lengths(rng, n: int, args) -> list[int]:
    """``n`` live lengths: a prompt plus a uniform share of its answer."""
    out = []
    for _ in range(n):
        p = args.prompt_median * math.exp(args.prompt_sigma * rng.standard_normal())
        o = args.output_median * math.exp(args.output_sigma * rng.standard_normal())
        p = min(max(round(p), args.prompt_min), args.prompt_max)
        o = min(max(round(o), args.output_min), args.output_max)
        out.append(p + int(rng.integers(1, o + 1)))
    return out


def build_tables(kv_lens: list[int], slots: int, bs: int, pool_blocks: int,
                 max_blocks: int, rng) -> tuple[np.ndarray, np.ndarray]:
    """Distinct random pool blocks for each live slot; idle slots and the
    padding point at the null block 0.  Returns ``(tables, kv_len)``."""
    need = [-(-n // bs) for n in kv_lens]
    width = max(need + [1])
    width = min(1 << (width - 1).bit_length(), max_blocks)
    tables = np.zeros((slots, width), np.int32)
    perm = rng.permutation(np.arange(1, pool_blocks))
    i = 0
    for s, b in enumerate(need):
        tables[s, :b] = perm[i:i + b]
        i += b
    kv_len = np.ones(slots, np.int32)
    kv_len[:len(kv_lens)] = kv_lens
    return tables, kv_len


def fit(rng, n: int, args) -> list[int]:
    """Redraw until the ``n`` lengths fit the pool and the table cap."""
    cap = args.max_blocks * args.block_size
    for _ in range(1000):
        lens = [min(x, cap) for x in live_lengths(rng, n, args)]
        if sum(-(-x // args.block_size) for x in lens) < args.pool_blocks:
            return lens
    raise SystemExit(f"{n} live slots of this mix do not fit "
                     f"{args.pool_blocks} blocks")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--heads", type=int, default=14)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--pool-blocks", type=int, default=2049)
    ap.add_argument("--max-blocks", type=int, default=288,
                    help="the server's per-slot table cap")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--active", default="16",
                    help="comma-separated live slot counts")
    ap.add_argument("--draws", type=int, default=2)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=4294967311)
    ap.add_argument("--prompt-median", type=float, default=512)
    ap.add_argument("--prompt-sigma", type=float, default=1.0)
    ap.add_argument("--prompt-min", type=int, default=32)
    ap.add_argument("--prompt-max", type=int, default=4096)
    ap.add_argument("--output-median", type=float, default=128)
    ap.add_argument("--output-sigma", type=float, default=0.8)
    ap.add_argument("--output-min", type=int, default=16)
    ap.add_argument("--output-max", type=int, default=512)
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpret mode (CPU smoke)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention import kernel as K
    from repro.kernels.paged_attention import paged_attention_ref

    S, H, Kh, dh, bs = (args.slots, args.heads, args.kv_heads, args.head_dim,
                        args.block_size)
    L, nb = args.layers, args.pool_blocks
    scale = dh ** -0.5
    rng = np.random.default_rng(args.seed % 2**63)
    key = jax.random.PRNGKey(args.seed % 2**31)
    kk, kv, kq = jax.random.split(key, 3)
    kp = jax.random.normal(kk, (L, nb, bs, Kh * dh), jnp.bfloat16)
    vp = jax.random.normal(kv, (L, nb, bs, Kh * dh), jnp.bfloat16)
    q = jax.random.normal(kq, (S, H, dh), jnp.bfloat16)

    @jax.jit
    def layers(q, kp, vp, tables, kv_len):
        def body(l, acc):
            return acc + K.paged_attention_pallas(
                q, kp, vp, tables, kv_len, scale=scale, layer=l,
                interpret=args.interpret).astype(jnp.float32)
        return jax.lax.fori_loop(0, L, body, jnp.zeros(q.shape, jnp.float32))

    @jax.jit
    def gap(q, kp, vp, tables, kv_len):
        lay = jnp.int32(L - 1)
        got = K.paged_attention_pallas(q, kp, vp, tables, kv_len, scale=scale,
                                       layer=lay, interpret=args.interpret)
        want = paged_attention_ref(q, kp, vp, tables, kv_len, scale=scale,
                                   layer=lay)
        return jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()

    walk = getattr(K, "walk_chunks", None)   # absent before the live walk
    for n_active in (int(a) for a in args.active.split(",")):
        for draw in range(args.draws):
            lens = fit(rng, n_active, args)
            tbl, kvl = build_tables(lens, S, bs, nb, args.max_blocks, rng)
            t, n = jnp.asarray(tbl), jnp.asarray(kvl)
            jax.block_until_ready(layers(q, kp, vp, t, n))
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = layers(q, kp, vp, t, n)
            jax.block_until_ready(out)
            call_s = (time.perf_counter() - t0) / (args.reps * L)
            live_bytes = 2.0 * Kh * dh * 2 * int(kvl.sum())
            chunks = (int(walk(kvl, block_size=bs, n_entries=tbl.shape[1]).sum())
                      if walk else None)
            print(json.dumps({
                "active": n_active, "draw": draw, "table_width": tbl.shape[1],
                "live_tokens": int(kvl.sum()), "us_per_call": call_s * 1e6,
                "live_gb_per_s": live_bytes / call_s / 1e9,
                "chunks_per_call": chunks,
                "us_per_chunk": call_s * 1e6 / chunks if chunks else None,
                "max_abs_gap": float(gap(q, kp, vp, t, n)),
                "device": jax.devices()[0].device_kind,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
