"""Paged-attention decode Pallas TPU kernel (live-block walk, no gather).

Decode attention for ``S`` serving slots directly against the physical KV
block pool: no dense ``[S, max_len, ...]`` view is ever materialized.  Layout:

    q       [S, H, dh] or [S, Q, H, dh]   Q query tokens per slot (Q > 1 is
                                          the speculative-decoding verify step)
    k_pool  [(n_layers,) num_blocks, bs, K*dh]    the physical pool
    v_pool  [(n_layers,) num_blocks, bs, K*dv]    (see PagedKVCache)
    tables  [S, M] int32          per-slot block tables (padding -> null 0)
    kv_len  [S] int32             live positions per slot (incl. all Q tokens)
    layer   scalar int32          pool layer for the 4-D layer-stacked layout

The walk.  The grid is one step per slot, run in order; ``tables``,
``kv_len`` and ``layer`` ride scalar prefetch into SMEM and the pools stay
in HBM.  Inside a slot a loop runs over the slot's *live* blocks only,
``pages`` table entries (256 key rows at ``bs`` 16) per iteration: from the
slot's first live block (block 0, or for windowed attention the block
holding the oldest query's window start) to its last, ``(kv_len - 1) //
bs``.  Each iteration's K and V rows are copied by DMA from the pool into a
two-deep VMEM buffer per side, one copy per table entry and only for the
entries that hold live rows, so an idle slot (``kv_len`` 1) moves one block
pair.  The next chunk's copies start before the current chunk is scored, and
a slot's last chunk starts the next slot's first, so the copies overlap the
MXU work and only slot 0 waits for its first.  Dead table entries cost
neither a copy nor a grid step.  All KV heads of a block come in one copy,
and the Q query rows share every fetched block: multi-token verification
costs the same HBM traffic as single-token decode.

TPU layout.  Mosaic lowers only 2-D (or leading-batch) matmuls and wants
every block's last two dims either (8, 128)-aligned or whole, so both sides
are laid out around the KV head:

* q rides as ``[S, K, Q*G, dh]`` — KV head leading, the ``G`` query heads of
  that group times the ``Q`` query tokens on the row axis (row ``r`` is query
  ``r // G``);
* the pool is *stored* as ``[(n,) num_blocks, bs, K*dh]`` (``PagedKVCache``
  flattens the trailing ``[K, dh]``) and taken as it is, so one table entry
  copies one dense ``(bs, K*dh)`` tile and KV head ``h`` is the lane slice
  ``[h*dh, (h+1)*dh)``.  ``K`` and ``dv`` follow from the shapes
  (``K*dh`` over q's ``dh``).  Reshaping a ``[.., K, dh]`` pool here instead
  is not free on the TPU: it relayouts the whole pool at every call.

The score and value matmuls then run per KV head as plain 2-D
``[Q*G, dh] x [P*bs, dh]^T`` and ``[Q*G, P*bs] x [P*bs, dv]`` float32
products.  Online softmax state (running max, denominator and unnormalized
float32 accumulator, per KV head) rides the loop carry; the output is
normalised once at the end.

Causal masking inside the query block: query ``i`` (0-based of Q) sits at
absolute position ``kv_len - Q + i`` and attends keys
``< kv_len - (Q - 1 - i)``; the window low bound shifts per query the same
way.  At Q = 1 both collapse to the plain decode masks.  Keys past the
table's reach (``M * bs``) are never read.

``walk_chunks`` is the host's copy of the walk's trip count, for the
serving loop's ``paged_decode_chunks`` counter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
CHUNK_ROWS = 256   # key rows a chunk: one MXU-wide score tile an iteration


def default_pages(block_size: int) -> int:
    return max(1, CHUNK_ROWS // block_size)


def _live_span(kvl, *, block_size: int, n_entries: int, q_len: int,
               window: int | None, xp):
    """First and last table entries holding a key that some query attends:
    block 0 (or the oldest query's window start) to ``(kv_len - 1) // bs``,
    within the table.  ``xp`` is ``jnp`` in the kernel, ``np`` on the host."""
    last = xp.minimum(xp.maximum(kvl - 1, 0) // block_size, n_entries - 1)
    first = xp.zeros_like(last)
    if window is not None:
        first = xp.minimum(
            xp.maximum(kvl - (q_len - 1) - window, 0) // block_size, last)
    return first, last


def walk_chunks(kv_len, *, block_size: int, n_entries: int, q_len: int = 1,
                window: int | None = None,
                pages: int | None = None) -> np.ndarray:
    """Chunks the kernel walks for each slot of ``kv_len`` (NumPy, host
    side): the chunks of ``pages`` table entries between the slot's first
    and last live block."""
    first, last = _live_span(
        np.asarray(kv_len, np.int64), block_size=block_size,
        n_entries=n_entries, q_len=q_len, window=window, xp=np)
    return (last - first) // (pages or default_pages(block_size)) + 1


def _paged_kernel(
    tbl_ref, len_ref, lay_ref,     # scalar prefetch: [S, M], [S], [1]
    q_ref,                         # [1, K, Q*G, dh]
    k_hbm, v_hbm,                  # the pools, left in HBM
    o_ref,                         # [1, K, Q*G, dv] float32
    kbuf, vbuf, sem, nxt,          # [2, P*bs, K*d*] x2, DMA [2, 2], SMEM [1]
    *, scale: float, window: int | None, block_size: int, pages: int,
    n_entries: int, n_kv: int, q_per_kv: int, q_len: int,
):
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    bs, P, M = block_size, pages, n_entries
    K, G, Q = n_kv, q_per_kv, q_len
    lay = lay_ref[0]

    def span(t):
        """Slot ``t``'s first and last live table entries."""
        return _live_span(len_ref[t], block_size=bs, n_entries=M, q_len=Q,
                          window=window, xp=jnp)

    def copies(t, c, slot, go):
        """Issue (``go`` "start") or retire ("wait") chunk ``c`` of slot
        ``t``: one K and one V copy per table entry that holds live rows."""
        first, last = span(t)
        e0 = first + c * P

        def page(p, carry):
            blk = tbl_ref[t, e0 + p]
            dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
            for i, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                cp = pltpu.make_async_copy(
                    hbm.at[lay, blk], buf.at[slot, dst], sem.at[i, slot])
                getattr(cp, go)()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(P, last - e0 + 1), page, 0)

    @pl.when(s == 0)
    def _first():
        nxt[0] = 0
        copies(0, 0, 0, "start")

    base = nxt[0]
    kvl = len_ref[s]
    first, last = span(s)
    n = (last - first) // P + 1
    rows = P * bs
    # keys past the table's reach are never read
    kend = jnp.minimum(kvl, M * bs)
    dh = q_ref.shape[-1]
    dv = o_ref.shape[-1]
    # per-query causal limit: row r is query r // G, which attends keys
    # < kvl - (Q - 1 - r // G)
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q * G, rows), 0) // G
    limit = jnp.minimum(kvl - (Q - 1) + qi, kend)
    qs = [q_ref[0, h].astype(jnp.float32) for h in range(K)]  # [Q*G, dh]

    def chunk(c, carry):
        slot = (base + c) % 2

        @pl.when(c + 1 < n)
        def _prefetch():
            copies(s, c + 1, 1 - slot, "start")

        @pl.when((c + 1 == n) & (s + 1 < n_slots))
        def _prefetch_next_slot():
            copies(s + 1, 0, 1 - slot, "start")

        copies(s, c, slot, "wait")
        start = (first + c * P) * bs
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (Q * G, rows), 1)
        mask = pos < limit
        if window is not None:
            mask &= pos >= kvl - (Q - 1) + qi - window
        kb = kbuf[slot].astype(jnp.float32)                 # [P*bs, K*dh]
        # rows of entries not copied hold stale VMEM: keep them out of p.V
        vrow = start + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        vb = jnp.where(vrow < kend, vbuf[slot].astype(jnp.float32), 0.0)
        out = []
        for h, (m_prev, l_prev, acc) in enumerate(carry):
            sc = jax.lax.dot_general(
                qs[h], kb[:, h * dh:(h + 1) * dh], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                        # [Q*G, P*bs]
            sc = jnp.where(mask, sc, NEG)
            m_new = jnp.maximum(m_prev, sc.max(-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
            out.append((
                m_new,
                l_prev * corr + p.sum(-1, keepdims=True),
                acc * corr + jnp.dot(p, vb[:, h * dv:(h + 1) * dv],
                                     preferred_element_type=jnp.float32),
            ))
        return tuple(out)

    init = tuple(
        (jnp.full((Q * G, 1), NEG, jnp.float32),
         jnp.zeros((Q * G, 1), jnp.float32),
         jnp.zeros((Q * G, dv), jnp.float32))
        for _ in range(K))
    state = jax.lax.fori_loop(0, n, chunk, init)
    nxt[0] = (base + n) % 2
    for h, (_, l, acc) in enumerate(state):
        o_ref[0, h] = acc / jnp.where(l == 0.0, 1.0, l)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "pages", "interpret")
)
def paged_attention_pallas(
    q: jax.Array,        # [S, H, dh] or [S, Q, H, dh]
    k_pool: jax.Array,   # [(n,) num_blocks, bs, K*dh]
    v_pool: jax.Array,   # [(n,) num_blocks, bs, K*dv]
    tables: jax.Array,   # [S, M] int32
    kv_len: jax.Array,   # [S] int32
    *,
    scale: float,
    window: int | None = None,
    pages: int | None = None,
    interpret: bool = False,
    layer: jax.Array | None = None,  # indexes layer-stacked 4-D pools
) -> jax.Array:
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    S, Q, H, dh = q.shape
    if k_pool.ndim == 3:  # single-layer pool: lift to the stacked layout
        k_pool, v_pool = k_pool[None], v_pool[None]
        layer = jnp.zeros((), jnp.int32)
    bs = k_pool.shape[2]
    K = k_pool.shape[-1] // dh
    dv = v_pool.shape[-1] // K
    M = tables.shape[1]
    G = H // K
    assert K * G == H, (H, K)
    P = pages or default_pages(bs)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    # KV head leading; its G query heads x Q query tokens on the row axis:
    # every fetched K/V block is scored against all Q*G rows at once
    qk = q.reshape(S, Q, K, G, dh).transpose(0, 2, 1, 3, 4)
    qk = qk.reshape(S, K, Q * G, dh)

    def slot_map(s, tbl, kvl, lay):
        return (s, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, K, Q * G, dh), slot_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, K, Q * G, dv), slot_map),
        scratch_shapes=[
            pltpu.VMEM((2, P * bs, K * dh), k_pool.dtype),
            pltpu.VMEM((2, P * bs, K * dv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, window=window, block_size=bs,
            pages=P, n_entries=M, n_kv=K, q_per_kv=G, q_len=Q,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, K, Q * G, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the kernel's name on the device trace, which the benchmark's
        # roofline reader matches
        name="paged_attention_pallas",
    )(tables.astype(jnp.int32), kv_len.astype(jnp.int32), lay, qk,
      k_pool, v_pool)
    o = out.reshape(S, K, Q, G, dv).transpose(0, 2, 1, 3, 4)
    o = o.reshape(S, Q, H, dv).astype(q.dtype)
    return o[:, 0] if squeeze else o
