"""Flash-prefill Pallas TPU kernel over the paged KV pool (q-block x kv-block).

One kernel for every q_len > 1 attention the serving engine runs — full
prefill, chunked prefill, and the Q = spec_k + 1 speculative verify step —
reading K/V *directly from physical pool blocks via the block table* exactly
like the decode kernel (`kernel.py`), but tiling the query axis too:

    grid (slot, q-block, table-entry)       table walk innermost/sequential

Layout matches the decode kernel:

    q       [S, Q, H, dh]   RAW post-projection queries (pre-norm, pre-rope)
    k_pool  [(n_layers,) num_blocks, bs, K*dh]
    v_pool  [(n_layers,) num_blocks, bs, K*dv]
    tables  [S, M] int32    per-slot block tables (padding -> null block 0)
    kv_len  [S] int32       live positions per slot incl. all Q new tokens
    layer   scalar int32    layer index for the 4-D layer-stacked pool layout

Fused q prologue: the rmsnorm (qwen3 ``qk_norm``) + rope entry into attention
is computed *inside the kernel* once per (slot, q-block) — at the first table
step the raw query tile is normalized, rotated with positions derived
in-kernel (query ``i`` of ``Q`` sits at absolute position ``kv_len - Q + i``,
so ``pos = kv_len - Q + q_block_lo + iota``), requantized through the model
dtype (bit-matching the jnp ``rms_head_norm``/``apply_rope`` chain, which
round-trips through ``x.dtype`` between the two), and parked in a VMEM
scratch tile that the whole kv sweep then reuses.  Prefill stops paying the
separate norm -> rope -> attention HBM round-trips of the generic path.

Causality is *per query inside the block*: query ``i`` attends keys
``< kv_len - (Q - 1 - i)`` (the decode kernel's verify mask, generalized by
the q-block offset), which at Q = full prompt length is plain causal prefill
and at Q = spec_k + 1 is the verify step.  The window mask shifts per query
the same way.

Early exit mirrors the decode kernel and adds the *causal upper clamp*: table
entries wholly above a q-block's highest query — the upper triangle of the
(q-block, kv-block) grid — are skipped by ``pl.when`` and their index maps
clamp onto the live band, so the pipeline never DMAs a block the masks would
zero out anyway.  Per-(slot, q-block) HBM traffic is O(causal reach), i.e.
full prefill costs ~half the dense quadratic sweep and chunked prefill costs
O(kv_len) not O(bucket ceiling).

TPU layout, as in the decode kernel: q rides KV-head-leading as
``[S, K, Q*G, dh]`` (row ``r`` of a head group is query ``r // G``) and the
pool, stored as ``[(n,) num_blocks, bs, K*dh]``, is taken as it is (no
whole-pool relayout per call), so every matmul is a 2-D product per KV
head.  Rope's rotate-half is a matmul with a signed permutation (exact: each
output lane picks one input lane), which keeps the prologue free of
half-width lane slices; the rope frequencies come in from the wrapper
(``rope_freqs``, duplicated over both halves) so the angles are the model's
own.

Online-softmax state (running max / denominator / unnormalized f32
accumulator) lives in VMEM scratch that persists across the table sweep of
one (slot, q-block); the last step normalizes into the output block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _prefill_kernel(
    tbl_ref, len_ref, lay_ref,     # scalar-prefetch: tables [S,M], kv_len [S],
    q_ref, qs_ref, fr_ref, rot_ref,  # layer [1]; q tile [1, K, QB*G, dh],
    k_ref, v_ref,                  #   q_norm scale [1, dh], rope freqs [1, dh],
    o_ref,                         #   rotate-half [dh, dh]; K/V [1,1,bs,K*d*]
    q_sc, m_sc, l_sc, acc_sc,      # scratch: prepared f32 q tile, softmax state
    *, scale: float, window: int | None, block_size: int,
    n_kv: int, q_per_kv: int, q_len: int, q_blk: int,
    has_qnorm: bool, eps: float,
):
    s = pl.program_id(0)
    iq = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    kvl = len_ref[s]
    K, G, Q, QB = n_kv, q_per_kv, q_len, q_blk
    R = QB * G
    qlo = iq * QB
    dh = q_ref.shape[-1]
    dv = acc_sc.shape[-1]
    # query index (within the whole Q) of every row of a head group
    qi = qlo + jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // G

    @pl.when(j == 0)
    def _prologue():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)
        # fused entry: rmsnorm (optional) + rope on the raw query tile, once
        # per (slot, q-block); requantize through the model dtype after each
        # stage so the result matches the jnp rms_head_norm/apply_rope chain
        # (each returns x.dtype) feeding the generic attention path
        ang = (kvl - Q + qi).astype(jnp.float32) * fr_ref[...]   # [R, dh]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        rot = rot_ref[...]
        for h in range(K):
            x = q_ref[0, h].astype(jnp.float32)                  # [R, dh]
            if has_qnorm:
                var = (x * x).mean(-1, keepdims=True)
                x = x * jax.lax.rsqrt(var + eps) * qs_ref[...].astype(
                    jnp.float32)
                x = x.astype(q_ref.dtype).astype(jnp.float32)
            xr = x * cos + jnp.dot(
                x, rot, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            ) * sin
            q_sc[h] = xr.astype(q_ref.dtype).astype(jnp.float32)

    # early exit: skip entries past this q-block's causal reach (upper
    # triangle) or the slot's live range; windowed families also skip entries
    # wholly before the block's oldest query's window
    hi = kvl - Q + qlo + QB          # exclusive key limit of the last query
    live = j * block_size < jnp.minimum(hi, kvl)
    if window is not None:
        live &= j * block_size + block_size > kvl - (Q - 1) + qlo - window

    @pl.when(live)
    def _accumulate():
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (R, block_size), 1
        )
        # per-query causal limit: query qi attends keys < kvl - (Q - 1 - qi)
        limit = kvl - (Q - 1) + qi
        mask = pos < limit
        if window is not None:
            mask &= pos > limit - 1 - window
        kb = k_ref[0, 0].astype(jnp.float32)                 # [bs, K*dh]
        vb = v_ref[0, 0].astype(jnp.float32)                 # [bs, K*dv]
        for h in range(K):
            sc = jax.lax.dot_general(
                q_sc[h], kb[:, h * dh:(h + 1) * dh], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                        # [R, bs]
            sc = jnp.where(mask, sc, NEG)
            m_prev = m_sc[h]                                 # [R, 1]
            m_new = jnp.maximum(m_prev, sc.max(-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
            l_sc[h] = l_sc[h] * corr + p.sum(-1, keepdims=True)
            m_sc[h] = m_new
            acc_sc[h] = acc_sc[h] * corr + jnp.dot(
                p, vb[:, h * dv:(h + 1) * dv],
                preferred_element_type=jnp.float32,
            )

    @pl.when(j == nj - 1)
    def _normalize():
        l = l_sc[...]
        o_ref[0] = acc_sc[...] / jnp.where(l == 0.0, 1.0, l)


def pick_q_block(q_len: int, q_block: int) -> int:
    """Largest usable q tile: ``q_block`` when it divides ``q_len`` (the
    pow2/bucketed prefill and chunk widths), else the whole query range (the
    Q = spec_k + 1 verify step degenerates to a single q-block)."""
    qb = min(q_block, q_len) if q_block else q_len
    return qb if q_len % qb == 0 else q_len


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "window", "interpret", "eps", "rope_theta", "q_block"
    ),
)
def paged_prefill_pallas(
    q: jax.Array,        # [S, Q, H, dh] raw (pre-norm, pre-rope) queries
    k_pool: jax.Array,   # [(n,) num_blocks, bs, K*dh], new K already written
    v_pool: jax.Array,   # [(n,) num_blocks, bs, K*dv]
    tables: jax.Array,   # [S, M] int32
    kv_len: jax.Array,   # [S] int32
    *,
    scale: float,
    window: int | None = None,
    interpret: bool = False,
    layer: jax.Array | None = None,  # indexes layer-stacked 4-D pools
    q_norm: jax.Array | None = None,  # [dh] qk_norm scale (None = no norm)
    eps: float = 1e-6,
    rope_theta: float = 10000.0,
    q_block: int = 32,
) -> jax.Array:
    from repro.models.layers import rope_freqs

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
    QB = pick_q_block(Q, q_block)
    nq = Q // QB
    half = dh // 2
    tables = tables.astype(jnp.int32)
    kv_len = kv_len.astype(jnp.int32)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    has_qnorm = q_norm is not None
    qs = (q_norm if has_qnorm else jnp.ones((dh,), q.dtype)).reshape(1, dh)
    fr = jnp.tile(rope_freqs(dh, rope_theta), 2).reshape(1, dh)
    # x @ rot == concat(-x2, x1): rotate-half as one exact matmul
    i = jnp.arange(dh)
    rot = (
        jnp.where(i[:, None] == i[None, :] + half, -1.0, 0.0)
        + jnp.where(i[:, None] + half == i[None, :], 1.0, 0.0)
    ).astype(jnp.float32)
    # KV head leading; q-block iq owns rows [iq*QB*G, (iq+1)*QB*G) of each
    # head group
    qk = q.reshape(S, Q, K, G, dh).transpose(0, 2, 1, 3, 4)
    qk = qk.reshape(S, K, Q * G, dh)

    def kv_map(s, iq, j, tbl, kvl, lay):
        # clamp dead entries onto the live causal band [first, lastq]: same
        # index as an adjacent step -> the pipeline skips the DMA instead of
        # streaming blocks the masks would zero (the upper triangle above
        # this q-block's reach, entries past the last live position, and —
        # for windowed attention — entries before the window's reach)
        last = jnp.maximum(kvl[s] - 1, 0) // bs
        hi = kvl[s] - Q + (iq + 1) * QB      # this q-block's causal limit
        lastq = jnp.minimum(jnp.maximum(hi - 1, 0) // bs, last)
        jj = jnp.minimum(j, lastq)
        if window is not None:
            first = jnp.maximum(kvl[s] - (Q - 1) + iq * QB - window, 0) // bs
            jj = jnp.maximum(jj, jnp.minimum(first, lastq))
        return (lay[0], tbl[s, jj], 0, 0)

    def q_map(s, iq, j, tbl, kvl, lay):
        return (s, 0, iq, 0)

    def const_map(s, iq, j, tbl, kvl, lay):
        return (0, 0)

    R = QB * G
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, nq, M),
        in_specs=[
            pl.BlockSpec((1, K, R, dh), q_map),
            pl.BlockSpec((1, dh), const_map),
            pl.BlockSpec((1, dh), const_map),
            pl.BlockSpec((dh, dh), const_map),
            pl.BlockSpec((1, 1, bs, K * dh), kv_map),
            pl.BlockSpec((1, 1, bs, K * dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, K, R, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((K, R, dh), jnp.float32),
            pltpu.VMEM((K, R, 1), jnp.float32),
            pltpu.VMEM((K, R, 1), jnp.float32),
            pltpu.VMEM((K, R, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, scale=scale, window=window, block_size=bs,
            n_kv=K, q_per_kv=G, q_len=Q, q_blk=QB, has_qnorm=has_qnorm,
            eps=eps,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, K, Q * G, dv), jnp.float32),
        interpret=interpret,
        # the kernel's name on the device trace, which the benchmark's
        # roofline reader matches
        name="paged_prefill_pallas",
    )(tables, kv_len, lay, qk, qs, fr, rot, k_pool, v_pool)
    o = out.reshape(S, K, Q, G, dv).transpose(0, 2, 1, 3, 4)
    return o.reshape(S, Q, H, dv).astype(q.dtype)
