"""Dispatching wrapper: model layout <-> kernel layout + the paged-view token.

``PagedInfo`` is the small pytree the serving engine threads through
``lm.forward`` down to ``layers.attention`` to flip a block from the dense
cached path onto the paged pool: the block's cache leaves then *are* pool
arrays ``[num_blocks, bs, F]`` (``F`` the flattened feature axes, ``K*dh``
for k/v; see ``PagedKVCache``) and attention walks ``tables`` instead of a
gathered dense view.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.latent_kernel import (
    paged_latent_attention_pallas,
)
from repro.kernels.paged_attention.prefill_kernel import paged_prefill_pallas
from repro.kernels.paged_attention.ref import (
    paged_attention_ref,
    paged_latent_attention_ref,
    paged_prefill_ref,
)


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class PagedInfo:
    """Paged-KV view descriptor: the per-slot block tables (traced; possibly
    sliced to the live-block high-water mark) plus static pool geometry and
    kernel dispatch choice.

    ``layer``, when set, marks the cache leaves as *whole layer-stacked*
    pools ``[n_layers, num_blocks, bs, F]`` indexed at that layer —
    ``lm.forward`` threads the stacked pools through its scan carry (updated
    in place via layer-indexed scatters) instead of slicing them into scan
    xs/ys, which would re-stack the full pool every decode step."""

    tables: jax.Array       # [S, M] int32, padding entries -> null block 0
    block_size: int
    impl: str = "auto"      # auto | xla | pallas | pallas_interpret
    layer: jax.Array | None = None  # scalar layer index into stacked pools
    # prefill=True flips attention blocks with seq > 1 onto the fused
    # flash-prefill path (`paged_prefill`): norm+rope+scatter+attention in
    # one op against the pool, instead of the generic dense-cache branch.
    # The decode/verify distinction stays dynamic-free: q_len == 1 keeps the
    # decode kernel regardless.
    prefill: bool = False
    # static absolute position of the first query when uniform across slots
    # (the full-prefill step pins 0): unlocks the causal band in the ref
    # oracle so its gather cost tracks the lower triangle, not the table
    q_start: int | None = None

    def tree_flatten(self):
        return (self.tables, self.layer), (
            self.block_size, self.impl, self.prefill, self.q_start,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        tables, layer = children
        return cls(tables, aux[0], aux[1], layer, *aux[2:])


def paged_attention(
    q: jax.Array,        # [S, Q, H, dh] (model layout; Q > 1 = spec-decode
                         #   verify) or [S, H, dh] (bare single-token)
    k_pool: jax.Array,   # [(n_layers,) num_blocks, bs, K*dh]
    v_pool: jax.Array,   # [(n_layers,) num_blocks, bs, K*dv]
    *,
    tables: jax.Array,   # [S, M] int32
    kv_len: jax.Array,   # [S] int32 (live positions incl. all Q new tokens)
    scale: float,
    window: int | None = None,
    impl: str = "auto",
    layer: jax.Array | None = None,  # required for layer-stacked (4-D) pools
) -> jax.Array:
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    fn = paged_attention_ref if impl == "xla" else functools.partial(
        paged_attention_pallas, interpret=(impl == "pallas_interpret")
    )
    return fn(
        q, k_pool, v_pool, tables, kv_len, scale=scale, window=window,
        layer=layer,
    )


def paged_latent_attention(
    q: jax.Array,         # [S, H, W]  [q_nope W_uk | q_pe | zeros]
    pool: jax.Array,      # [(n_layers,) num_blocks, bs, W]
    *,
    tables: jax.Array,    # [S, M] int32
    kv_len: jax.Array,    # [S] int32
    r: int,               # value width (kv_lora_rank)
    scale: float,
    impl: str = "auto",
    layer: jax.Array | None = None,
) -> jax.Array:
    """Absorbed MLA decode against the latent pool: the float32 context
    ``[S, H, r]`` (``latent_kernel.py``; the XLA ref off the TPU)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return paged_latent_attention_ref(
            q, pool, tables, kv_len, r=r, scale=scale, layer=layer)
    return paged_latent_attention_pallas(
        q, pool, tables, kv_len, r=r, scale=scale,
        interpret=(impl == "pallas_interpret"), layer=layer)


def paged_prefill(
    q: jax.Array,        # [S, Q, H, dh] raw post-projection queries
    kk: jax.Array,       # [S, Q, K, dh] raw post-projection keys
    vv: jax.Array,       # [S, Q, K, dv] values
    k_pool: jax.Array,   # [(n_layers,) num_blocks, bs, K*dh]
    v_pool: jax.Array,   # [(n_layers,) num_blocks, bs, K*dv]
    *,
    tables: jax.Array,   # [S, M] int32
    positions: jax.Array,  # [S, Q] int32 contiguous write positions per slot
    block_size: int,
    scale: float,
    window: int | None = None,
    impl: str = "auto",
    layer: jax.Array | None = None,
    q_norm: jax.Array | None = None,  # [dh] qk_norm scales (None = off)
    k_norm: jax.Array | None = None,
    eps: float = 1e-6,
    rope_theta: float = 10000.0,
    q_start: int | None = None,
    q_block: int = 32,
) -> tuple[jax.Array, dict]:
    """Fused paged prefill: norm+rope the new K, scatter K/V into the pool
    blocks owning each slot's write positions, then flash-attend the Q query
    rows against the pool through the block table — full prefill, chunked
    prefill, and the spec-decode verify step are all this one op at
    different Q.  Returns ``(attn_out, {"k": pool, "v": pool})``.

    The K-side entry (rmsnorm + rope + the bfloat16 quantization into the
    cache container) reuses the model's own helpers so pool contents are
    bit-identical to the generic `gqa_apply` paged branch; the q-side entry
    is fused *inside* the Pallas kernel (or applied with the same helpers on
    the XLA ref path).  Write positions beyond the table's reach redirect to
    the null block, exactly like the decode-step scatter.
    """
    # lazy import: layers imports this module (the dispatch is a leaf of the
    # model stack), so the model-side helpers load on first call only
    from repro.models.layers import apply_rope, rms_head_norm

    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"

    if k_norm is not None:
        kk = rms_head_norm(k_norm, kk, eps)
    kk = apply_rope(kk, positions, rope_theta)
    pos = positions
    bs = block_size
    in_reach = pos < tables.shape[1] * bs
    blk = jnp.where(in_reach, pos // bs, 0)
    phys = jnp.take_along_axis(tables, blk, axis=1)          # [S, Q]
    phys = jnp.where(in_reach, phys, 0)
    off = pos % bs
    # [S, Q, K*d] rows: the pool stores each position's heads flattened
    k_new = kk.reshape(*kk.shape[:2], -1).astype(jnp.bfloat16)
    v_new = vv.reshape(*vv.shape[:2], -1).astype(jnp.bfloat16)
    k_new = k_new.astype(k_pool.dtype)
    v_new = v_new.astype(v_pool.dtype)
    if layer is None:
        ck = k_pool.at[phys, off].set(k_new)
        cv = v_pool.at[phys, off].set(v_new)
    else:  # layer-stacked pools riding lm.forward's scan carry
        ck = k_pool.at[layer, phys, off].set(k_new)
        cv = v_pool.at[layer, phys, off].set(v_new)
    kv_len = pos[:, -1] + 1

    if impl == "xla":
        qq = q if q_norm is None else rms_head_norm(q_norm, q, eps)
        qq = apply_rope(qq, positions, rope_theta)
        o = paged_prefill_ref(
            qq, ck, cv, tables, kv_len, scale=scale, window=window,
            layer=layer, q_start=q_start, q_block=q_block,
        )
    else:
        o = paged_prefill_pallas(
            q, ck, cv, tables, kv_len, scale=scale, window=window,
            interpret=(impl == "pallas_interpret"), layer=layer,
            q_norm=q_norm, eps=eps, rope_theta=rope_theta, q_block=q_block,
        )
    return o, {"k": ck, "v": cv}
