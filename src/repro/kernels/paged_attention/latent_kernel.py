"""Paged latent (MLA) decode attention, Pallas TPU kernel.

Absorbed multi-head latent attention for ``S`` serving slots straight
against the latent block pool.  A pool row is one cached token:
``[ckv (r) | k_pe (dr) | zeros]``, ``W`` wide, ``W`` the lane multiple at or
above ``r + dr`` (576 -> 640 for DeepSeek-V2; a 64-wide ``k_pe`` leaf of
its own would take the same 128 lanes in HBM, and a DMA may not slice a
tile's lanes).  The query comes in the same layout:

    q       [S, H, W]        [q_nope W_uk (r) | q_pe (dr) | zeros], per head
    pool    [(n,) blocks, bs, W]
    tables  [S, M] int32     per-slot block tables (padding -> null block 0)
    kv_len  [S] int32        live positions per slot
    layer   scalar int32     the pool layer (layer-stacked pools)

    out     [S, H, r] float32   softmax(scale q rows^T) rows[:, :r]

so one matmul gives ``q_lat ckv^T + q_pe k_pe^T``.  It is MQA with keys
``r + dr`` wide and values ``r`` wide: all H heads share every fetched block,
so each block is read once per slot per layer.

The walk.  The grid is one step per slot, run in order.  Inside it a loop
runs over the slot's *live* blocks only, ``pages`` table entries per
iteration: their rows are copied by DMA from the pool (the table rides
scalar prefetch into SMEM) into a two-deep VMEM buffer, the next chunk's
copies started before the current chunk is scored, so the copies overlap the
MXU work; a slot's last iteration starts the next slot's first chunk, so no
slot but the first waits for its first copies.  Dead table entries cost
neither a copy nor a grid step: the trip count is
``ceil(kv_len / (pages * bs))``.  Online softmax state (running max,
denominator, float32 context) rides the loop carry; the output is
normalised once at the end.

Scores and context take the pool's dtype (bfloat16 on the chip) with float32
accumulation, as the gathered path's einsums do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _latent_kernel(
    tbl_ref, len_ref, lay_ref,          # scalar prefetch: [S, M], [S], [1]
    q_ref,                              # [1, H, W]
    pool_hbm,                           # the pool, left in HBM
    o_ref,                              # [1, H, r] float32
    buf, sem, nxt,                      # [2, P*bs, W], DMA [2], SMEM [1]
    *, scale: float, block_size: int, pages: int, n_entries: int, r: int,
):
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    bs, P = block_size, pages
    lay = lay_ref[0]

    def n_chunks(t):
        return jnp.maximum((len_ref[t] + P * bs - 1) // (P * bs), 1)

    def copies(t, c, slot):
        out = []
        for p in range(P):
            blk = tbl_ref[t, jnp.minimum(c * P + p, n_entries - 1)]
            out.append(pltpu.make_async_copy(
                pool_hbm.at[lay, blk], buf.at[slot, pl.ds(p * bs, bs)],
                sem.at[slot]))
        return out

    # the grid runs slot after slot: each slot's last iteration starts the
    # next slot's first chunk, so only slot 0 waits for its first copies;
    # ``nxt`` carries which buffer that chunk went to
    @pl.when(s == 0)
    def _first():
        nxt[0] = 0
        for cp in copies(0, 0, 0):
            cp.start()

    base = nxt[0]
    kvl = len_ref[s]
    n = n_chunks(s)
    q = q_ref[0]                                            # [H, W]
    H = q.shape[0]

    def chunk(c, carry):
        m_prev, l_prev, acc = carry
        slot = (base + c) % 2

        @pl.when(c + 1 < n)
        def _prefetch():
            for cp in copies(s, c + 1, 1 - slot):
                cp.start()

        @pl.when((c + 1 == n) & (s + 1 < n_slots))
        def _prefetch_next_slot():
            for cp in copies(s + 1, 0, 1 - slot):
                cp.start()

        for cp in copies(s, c, slot):
            cp.wait()
        rows = buf[slot]                                    # [P*bs, W]
        sc = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [H, P*bs]
        pos = c * P * bs + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        live = pos < kvl
        sc = jnp.where(live, sc, NEG)
        m_new = jnp.maximum(m_prev, sc.max(-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(sc - m_new), 0.0)
        l_new = l_prev * corr + p.sum(-1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(rows.dtype), rows[:, :r],
                                   preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((H, 1), NEG, jnp.float32), jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, r), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n, chunk, init)
    nxt[0] = (base + n) % 2
    o_ref[0] = acc / jnp.where(l == 0.0, 1.0, l)


@functools.partial(jax.jit,
                   static_argnames=("r", "scale", "pages", "interpret"))
def paged_latent_attention_pallas(
    q: jax.Array,         # [S, H, W]
    pool: jax.Array,      # [(n,) blocks, bs, W]
    tables: jax.Array,    # [S, M] int32
    kv_len: jax.Array,    # [S] int32
    *,
    r: int,
    scale: float,
    pages: int | None = None,
    interpret: bool = False,
    layer: jax.Array | None = None,
) -> jax.Array:
    if pool.ndim == 3:  # single-layer pool: lift to the stacked layout
        pool = pool[None]
        layer = jnp.zeros((), jnp.int32)
    S, H, W = q.shape
    bs = pool.shape[2]
    M = tables.shape[1]
    # about 256 key rows a chunk: one MXU-wide score tile per iteration
    P = pages or max(1, 256 // bs)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda s, tbl, kvl, lay: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, r), lambda s, tbl, kvl, lay: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P * bs, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, block_size=bs,
                          pages=P, n_entries=M, r=r),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the kernel's name on the device trace, which the benchmark's
        # roofline reader matches
        name="paged_latent_attention_pallas",
    )(tables.astype(jnp.int32), kv_len.astype(jnp.int32), lay,
      q.astype(pool.dtype), pool)
