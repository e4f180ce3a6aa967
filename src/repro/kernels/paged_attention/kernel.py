"""Paged-attention decode Pallas TPU kernel (block-table walk, no gather).

Decode attention for ``S`` serving slots directly against the physical KV
block pool: no dense ``[S, max_len, ...]`` view is ever materialized.  Layout:

    q       [S, H, dh] or [S, Q, H, dh]   Q query tokens per slot (Q > 1 is
                                          the speculative-decoding verify step)
    k_pool  [(n_layers,) num_blocks, bs, K*dh]    the physical pool
    v_pool  [(n_layers,) num_blocks, bs, K*dv]    (see PagedKVCache)
    tables  [S, M] int32          per-slot block tables (padding -> null 0)
    kv_len  [S] int32             live positions per slot (incl. all Q tokens)
    layer   scalar int32          pool layer for the 4-D layer-stacked layout
                                  (rides scalar prefetch into the index maps,
                                  so the stacked pool is never sliced in HBM)

Grid ``(slot, table-entry)`` with the table walk innermost/sequential; the
``tables`` and ``kv_len`` arrays ride scalar prefetch
(``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index maps resolve
``tables[s, j]`` *before* the body runs and each step DMAs exactly one
physical block out of the pool.  All KV heads of a block are fetched in one
block (grid iterates table entries, not kv-heads: each block is touched once
per slot instead of once per head).  The Q query rows share
every fetched K/V block: multi-token verification costs the same HBM traffic
as single-token decode.

TPU layout.  Mosaic lowers only 2-D (or leading-batch) matmuls and wants
every block's last two dims either (8, 128)-aligned or whole, so both sides
are laid out around the KV head:

* q rides as ``[S, K, Q*G, dh]`` — KV head leading, the ``G`` query heads of
  that group times the ``Q`` query tokens on the row axis (row ``r`` is query
  ``r // G``);
* the pool is *stored* as ``[(n,) num_blocks, bs, K*dh]`` (``PagedKVCache``
  flattens the trailing ``[K, dh]``) and taken as it is, so one table entry
  DMAs one dense ``(bs, K*dh)`` tile and KV head ``h`` is the lane slice
  ``[h*dh, (h+1)*dh)``.  ``K`` and ``dv`` follow from the shapes
  (``K*dh`` over q's ``dh``).  Reshaping a ``[.., K, dh]`` pool here instead
  is not free on the TPU: it relayouts the whole pool at every call.

The score and value matmuls then run per KV head as plain 2-D
``[Q*G, dh] x [bs, dh]^T`` and ``[Q*G, bs] x [bs, dv]`` products.

Online softmax state (running max / denominator / unnormalized f32
accumulator) lives in VMEM scratch that persists across the sequential table
sweep; the last step normalizes into the output block, whose index map
ignores ``j``.

Causal masking inside the query block: query ``i`` (0-based of Q) sits at
absolute position ``kv_len - Q + i`` and attends keys
``< kv_len - (Q - 1 - i)``; the window low bound shifts per query the same
way.  At Q = 1 both collapse to the plain decode masks.

Early exit: entries at or past a slot's last live block — and, for windowed
attention, entries wholly before the *oldest* query's window reach —
contribute nothing: ``pl.when`` skips their compute *and* the index map clamps
onto the live range so the pipeline re-fetches a resident block instead of
streaming dead pool blocks.  Per-slot HBM traffic is therefore O(kv_len)
(O(window + Q) for windowed families), not O(max_len); the caller is still
free to slice ``tables`` down to the live-block high-water mark so the grid
itself shrinks too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _paged_kernel(
    tbl_ref, len_ref, lay_ref,     # scalar-prefetch: tables [S,M], kv_len [S],
    q_ref, k_ref, v_ref,           #   layer [1]; then q [1, K, Q*G, dh] and
    o_ref,                         #   the K/V tiles [1, 1, bs, K*d*]; output
    m_sc, l_sc, acc_sc,            # scratch: [K, Q*G, 1] x2, [K, Q*G, dv]
    *, scale: float, window: int | None, block_size: int,
    n_kv: int, q_per_kv: int, q_len: int,
):
    s = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    kvl = len_ref[s]
    K, G, Q = n_kv, q_per_kv, q_len
    dh = q_ref.shape[-1]
    dv = acc_sc.shape[-1]

    # early exit: skip table entries past the last live position, and — for
    # windowed attention — entries wholly before the oldest query's reach
    live = j * block_size < kvl
    if window is not None:
        live &= j * block_size + block_size > kvl - (Q - 1) - window

    @pl.when(live)
    def _accumulate():
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (Q * G, block_size), 1
        )
        # per-query causal limit: row r is query r // G, which attends keys
        # < kvl - (Q - 1 - r // G)
        qi = jax.lax.broadcasted_iota(jnp.int32, (Q * G, block_size), 0) // G
        limit = kvl - (Q - 1) + qi
        mask = pos < limit
        if window is not None:
            mask &= pos > limit - 1 - window
        kb = k_ref[0, 0].astype(jnp.float32)                 # [bs, K*dh]
        vb = v_ref[0, 0].astype(jnp.float32)                 # [bs, K*dv]
        for h in range(K):
            q = q_ref[0, h].astype(jnp.float32)              # [Q*G, dh]
            sc = jax.lax.dot_general(
                q, kb[:, h * dh:(h + 1) * dh], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                        # [Q*G, bs]
            sc = jnp.where(mask, sc, NEG)
            m_prev = m_sc[h]                                 # [Q*G, 1]
            m_new = jnp.maximum(m_prev, sc.max(-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
            l_sc[h] = l_sc[h] * corr + p.sum(-1, keepdims=True)
            m_sc[h] = m_new
            # the accumulator stays f32: re-quantizing it through the model
            # dtype every block step would compound bf16 rounding over long
            # kv_lens and drift off the gathered-dense oracle
            acc_sc[h] = acc_sc[h] * corr + jnp.dot(
                p, vb[:, h * dv:(h + 1) * dv],
                preferred_element_type=jnp.float32,
            )

    @pl.when(j == nj - 1)
    def _normalize():
        l = l_sc[...]
        o_ref[0] = acc_sc[...] / jnp.where(l == 0.0, 1.0, l)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "interpret")
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
    tables = tables.astype(jnp.int32)
    kv_len = kv_len.astype(jnp.int32)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    # KV head leading; its G query heads x Q query tokens on the row axis:
    # every fetched K/V block is scored against all Q*G rows at once
    qk = q.reshape(S, Q, K, G, dh).transpose(0, 2, 1, 3, 4)
    qk = qk.reshape(S, K, Q * G, dh)

    def kv_map(s, j, tbl, kvl, lay):
        # clamp dead entries onto the live range [first, last]: same index as
        # an adjacent step -> the pipeline skips the DMA instead of streaming
        # blocks the body would ignore anyway (past the last live position,
        # or — for windowed attention — wholly before the window's reach)
        last = jnp.maximum(kvl[s] - 1, 0) // bs
        jj = jnp.minimum(j, last)
        if window is not None:
            first = jnp.maximum(kvl[s] - (Q - 1) - window, 0) // bs
            jj = jnp.maximum(jj, jnp.minimum(first, last))
        return (lay[0], tbl[s, jj], 0, 0)

    def slot_map(s, j, tbl, kvl, lay):
        return (s, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, M),
        in_specs=[
            pl.BlockSpec((1, K, Q * G, dh), slot_map),
            pl.BlockSpec((1, 1, bs, K * dh), kv_map),
            pl.BlockSpec((1, 1, bs, K * dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, K, Q * G, dv), slot_map),
        scratch_shapes=[
            pltpu.VMEM((K, Q * G, 1), jnp.float32),
            pltpu.VMEM((K, Q * G, 1), jnp.float32),
            pltpu.VMEM((K, Q * G, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, window=window, block_size=bs,
            n_kv=K, q_per_kv=G, q_len=Q,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, K, Q * G, dv), jnp.float32),
        interpret=interpret,
        # the kernel's name on the device trace, which the benchmark's
        # roofline reader matches
        name="paged_attention_pallas",
    )(tables, kv_len, lay, qk, k_pool, v_pool)
    o = out.reshape(S, K, Q, G, dv).transpose(0, 2, 1, 3, 4)
    o = o.reshape(S, Q, H, dv).astype(q.dtype)
    return o[:, 0] if squeeze else o
