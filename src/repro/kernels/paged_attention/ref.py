"""XLA reference / fallback for the paged-attention decode kernel.

Gathers each slot's live blocks out of the pool (``k_pool[tables]`` — table
width, not pool size, bounds the traffic) and mirrors the naive masked-softmax
decode attention in ``models.layers.attention`` operation-for-operation: same
einsum labels, same ``BIG_NEG`` masking, same ``p.astype(v.dtype)`` cast, same
f32 accumulation.  Padding positions get exactly-zero probabilities, so the
output is invariant to the table width — which makes this both the interpret-
mode parity oracle for ``kernel.py`` and the serving fast path on non-TPU
backends (the caller slices ``tables`` to the live-block high-water mark, so
cost tracks kv_len, not pool max_len).

``q`` may carry more than one query per slot (``[S, Q, H, dh]``): the
speculative-decoding verify step scores Q = draft_len + 1 positions per slot
in one call.  Query ``i`` (0-based) sits at absolute position
``kv_len - Q + i`` and therefore attends keys ``< kv_len - (Q - 1 - i)`` —
causal masking *inside* the query block; at Q = 1 this degenerates to the
plain decode mask.  The window mask shifts per query the same way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30


def paged_attention_ref(
    q: jax.Array,        # [S, H, dh] or [S, Q, H, dh]
    k_pool: jax.Array,   # [(n,) num_blocks, bs, K*dh]
    v_pool: jax.Array,   # [(n,) num_blocks, bs, K*dv]
    tables: jax.Array,   # [S, M] int32
    kv_len: jax.Array,   # [S] int32, live positions incl. all Q new tokens
    *,
    scale: float,
    window: int | None = None,
    layer: jax.Array | None = None,  # indexes layer-stacked 4-D pools
) -> jax.Array:
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    S, Q, H, dh = q.shape
    bs = k_pool.shape[-2]
    K = k_pool.shape[-1] // dh
    dv = v_pool.shape[-1] // K
    M = tables.shape[1]
    G = H // K
    flat = tables.reshape(-1)
    if k_pool.ndim == 4:
        # one fused (layer, block) gather — never materializes a layer slice
        k = k_pool[layer, flat]
        v = v_pool[layer, flat]
    else:
        k = jnp.take(k_pool, flat, axis=0)
        v = jnp.take(v_pool, flat, axis=0)
    k = k.reshape(S, M * bs, K, dh).astype(q.dtype)
    v = v.reshape(S, M * bs, K, dv).astype(q.dtype)

    qg = q.reshape(S, Q, K, G, dh)
    s = jnp.einsum(
        "bskgd,btkd->bskgt", qg, k, preferred_element_type=jnp.float32
    ) * scale                                              # [S, Q, K, G, T]
    pos = jnp.arange(M * bs)[None, None, :]                # key positions
    # per-query causal limit: query i attends keys < kv_len - (Q - 1 - i)
    limit = kv_len[:, None] - (Q - 1 - jnp.arange(Q))[None, :]  # [S, Q]
    mask = pos < limit[:, :, None]
    if window is not None:
        mask &= pos > limit[:, :, None] - 1 - window
    s = jnp.where(mask[:, :, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bskgt,btkd->bskgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    o = o.reshape(S, Q, H, dv).astype(q.dtype)
    return o[:, 0] if squeeze else o


def paged_prefill_ref(
    q: jax.Array,        # [S, Q, H, dh], already normed + roped
    k_pool: jax.Array,   # [(n,) num_blocks, bs, K*dh]
    v_pool: jax.Array,   # [(n,) num_blocks, bs, K*dv]
    tables: jax.Array,   # [S, M] int32
    kv_len: jax.Array,   # [S] int32, live positions incl. all Q new tokens
    *,
    scale: float,
    window: int | None = None,
    layer: jax.Array | None = None,
    q_start: int | None = None,  # static absolute position of query 0 (all
                                 # slots); unlocks the causal band
    q_block: int = 32,
) -> jax.Array:
    """Banded q-block oracle for the flash-prefill kernel (`prefill_kernel`).

    Splits the Q query rows into static q-blocks and scores each against
    only the table prefix its causal reach can see: with ``q_start`` known
    (the full-prefill step pins query 0 at absolute position 0), q-block
    ``iq`` gathers ``ceil((q_start + (iq+1)*QB) / bs)`` table entries — the
    lower-triangular band, ~half the dense quadratic gather.  Without a
    static start (chunk/verify calls, where cache_len is traced) every block
    sees the full table width and per-query limits alone carry causality.

    Exactness of the banding: every excluded key position lies at or above
    the block's highest causal limit, so in the full computation its masked
    score contributes an exactly-zero probability (``exp(NEG - m)``
    underflows in f32) — banding changes the result only through XLA's
    reduction-tree order (f32 ulp-level), never through which keys count.

    Each band delegates to :func:`paged_attention_ref` with the kv_len
    shifted to the block's top query (``kv_len - (Q - (iq+1)*QB)``), which
    reproduces the per-query limits ``kv_len - (Q - 1 - i)`` of the full
    call, window masks included.
    """
    S, Q, H, dh = q.shape
    bs = v_pool.shape[-2]
    M = tables.shape[1]
    qb = q_block if (q_block and Q % q_block == 0) else Q
    qb = min(qb, Q)
    outs = []
    for iq in range(Q // qb):
        hi = None if q_start is None else q_start + (iq + 1) * qb
        reach = M if hi is None else max(1, min(M, -(-hi // bs)))
        outs.append(paged_attention_ref(
            q[:, iq * qb:(iq + 1) * qb],
            k_pool, v_pool, tables[:, :reach],
            kv_len - (Q - (iq + 1) * qb),
            scale=scale, window=window, layer=layer,
        ))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def paged_latent_attention_ref(
    q: jax.Array,         # [S, H, W]  [q_nope W_uk | q_pe | zeros]
    pool: jax.Array,      # [(n,) num_blocks, bs, W]  [ckv | k_pe | zeros]
    tables: jax.Array,    # [S, M] int32
    kv_len: jax.Array,    # [S] int32
    *,
    r: int,
    scale: float,
    layer: jax.Array | None = None,
) -> jax.Array:
    """Oracle for the latent decode kernel (``latent_kernel.py``): gathers
    each slot's table out of the latent pool and mirrors the gathered
    path's absorbed attention in ``models.layers.mla_apply`` (scores
    ``q_lat ckv^T + q_pe k_pe^T`` as one product over the row, masked
    softmax, context over ``ckv``, each einsum in the query's dtype with
    float32 accumulation).  Returns the float32 context ``[S, H, r]``."""
    S, H, W = q.shape
    bs = pool.shape[-2]
    M = tables.shape[1]
    flat = tables.reshape(-1)
    rows = pool[layer, flat] if pool.ndim == 4 else jnp.take(pool, flat, 0)
    rows = rows.reshape(S, M * bs, W).astype(q.dtype)
    s = jnp.einsum("shw,stw->sht", q, rows,
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(M * bs)[None, None, :] < kv_len[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, NEG), axis=-1)
    return jnp.einsum("sht,str->shr", p.astype(q.dtype), rows[..., :r],
                      preferred_element_type=jnp.float32)
