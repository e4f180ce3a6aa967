"""Core transformer building blocks (pure JAX, logical-axis annotated).

Sharding conventions (see repro/parallel/profiles.py for the rule tables):

Weight logical axes (suffix ``_w``): ``embed_w`` (FSDP dim), ``heads_w`` /
``kv_heads_w`` / ``head_dim_w`` / ``mlp_w`` / ``vocab_w`` / ``expert_w`` /
``kv_lora_w`` — tensor-parallel dims with size-aware fallback (e.g. 40 heads on
a 16-way axis falls through to sharding ``head_dim_w``).

Activation logical axes: ``batch``, ``seq_act`` (residual stream; sharded over
"model" in the context-parallel profile), ``seq`` (query positions inside
attention), ``seq_kv`` (gathered key/value positions), ``heads_act``,
``mlp_act``, ``kv_time`` (decode cache time dim), ``vocab_act`` (logit chunks),
``ce_batch`` (cross-entropy batch).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.hooks import Collector, NULL_COLLECTOR
from repro.parallel.sharding import shard_act

BIG_NEG = -1e30


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------


class ParamBuilder:
    """Builds a params pytree and its mirrored logical-axes pytree in lockstep."""

    def __init__(self, key: jax.Array, dtype: Any = jnp.float32):
        self._key = key
        self.dtype = dtype
        self.params: dict[str, Any] = {}
        self.axes: dict[str, Any] = {}

    def split(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k

    def param(
        self,
        name: str,
        shape: tuple[int, ...],
        axes: tuple[str | None, ...],
        init: str = "normal",
        fan_in: int | None = None,
        scale: float = 1.0,
        fill: float = 0.0,
    ) -> None:
        assert len(shape) == len(axes), (name, shape, axes)
        if init == "normal":
            fi = fan_in if fan_in is not None else shape[0]
            std = scale / math.sqrt(max(fi, 1))
            val = jax.random.normal(self.split(), shape, self.dtype) * jnp.asarray(
                std, self.dtype
            )
        elif init == "zeros":
            val = jnp.zeros(shape, self.dtype)
        elif init == "ones":
            val = jnp.ones(shape, self.dtype)
        elif init == "const":
            val = jnp.full(shape, fill, self.dtype)
        elif init == "uniform":
            val = jax.random.uniform(
                self.split(), shape, self.dtype, minval=-scale, maxval=scale
            )
        else:
            raise ValueError(init)
        self.params[name] = val
        self.axes[name] = axes

    def sub(self, name: str) -> "ParamBuilder":
        child = ParamBuilder(self.split(), self.dtype)
        self.params[name] = child.params
        self.axes[name] = child.axes
        return child

    def done(self) -> tuple[dict, dict]:
        return self.params, self.axes


def cast(p, dtype):
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, p)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_init(b: ParamBuilder, name: str, dim: int, kind: str, axis_name: str = "embed_w"):
    s = b.sub(name)
    s.param("scale", (dim,), (axis_name,), init="ones")
    if kind == "layernorm":
        s.param("bias", (dim,), (axis_name,), init="zeros")


def norm_apply(p: dict, x: jax.Array, kind: str, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        xf = xf - mu
    var = (xf * xf).mean(-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if kind == "layernorm":
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(scale: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """Per-head-dim RMSNorm (qwen3 qk_norm): x [..., dh], scale [dh]."""
    xf = x.astype(jnp.float32)
    var = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (1-D and multimodal 3-D)
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_ramp_range(dim: int, theta: float, scaling) -> tuple[int, int]:
    """The frequency indices between which YaRN blends extrapolated into
    interpolated frequencies: floor / ceil of the dimensions that turn
    ``beta_fast`` / ``beta_slow`` times over the original context."""

    def corr_dim(rotations: float) -> float:
        n = scaling.original_max_position_embeddings
        return (dim * math.log(n / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = math.floor(corr_dim(scaling.beta_fast))
    high = math.ceil(corr_dim(scaling.beta_slow))
    return max(low, 0), min(high, dim - 1)


def rope_freqs(dim: int, theta: float, scaling=None) -> jax.Array:
    """Inverse frequencies of rope; with ``scaling`` (a ``RopeScaling``) the
    YaRN blend: index i keeps ``theta^(-2i/dim)`` below the ramp, takes it
    divided by ``factor`` above it, and mixes the two linearly between."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if scaling is None:
        return freqs
    low, high = yarn_ramp_range(dim, theta, scaling)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x [B, S, H, D] or [B, S, D]; positions [S] shared or [B, S] per-row
    absolute positions (the paged decode step carries one position per slot).
    ``scaling`` (a ``RopeScaling``) switches to YaRN frequencies, with cos and
    sin multiplied by ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)``."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)  # [d/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [(B,) S, d/2]
    if x.ndim == 4:  # head dim present: [B, S, H, D]
        ang = ang[..., None, :]  # [(B,) S, 1, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, position_ids: jax.Array, sections: tuple[int, ...], theta: float
) -> jax.Array:
    """M-RoPE: x [B, S, H, D]; position_ids [3, B, S]; sections sum to D/2."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # [d/2]
    # Build per-frequency position selection: frequencies are split into
    # (t, h, w) sections; each section rotates with its own position stream.
    sec_id = jnp.concatenate(
        [jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)]
    )  # [d/2]
    pos = position_ids.astype(jnp.float32)  # [3, B, S]
    # [B, S, d/2]: pick position component per frequency
    pos_sel = jnp.take(pos, sec_id, axis=0)  # [d/2, B, S] -> want [B,S,d/2]
    pos_sel = jnp.moveaxis(pos_sel, 0, -1)
    ang = pos_sel * freqs  # [B, S, d/2]
    ang = ang[:, :, None, :]  # heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core (online-softmax chunked / local-block / decode / naive)
# ---------------------------------------------------------------------------


def _mask(
    pq: jax.Array,  # [S] or [B,S] query absolute positions
    pk: jax.Array,  # [C] key absolute positions
    causal: bool,
    window: int | None,
    kv_len: jax.Array | None,  # scalar or [B]
) -> jax.Array:
    """Returns boolean mask broadcastable to [B?, S, C]: True = attend."""
    q = pq[..., :, None]
    k = pk[None, :]
    m = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if causal:
        m &= k <= q
    if window is not None:
        m &= k > q - window
    if kv_len is not None:
        kl = jnp.asarray(kv_len)
        if kl.ndim == 1:  # per-batch
            m = m & (k < kl[:, None, None])
        else:
            m = m & (k < kl)
    return m


def attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, T, K, D]
    v: jax.Array,  # [B, T, K, D]
    *,
    scale: float,
    positions_q: jax.Array,  # [S] absolute positions of queries
    causal: bool = True,
    window: int | None = None,
    kv_len: jax.Array | None = None,
    impl: str = "chunked",
    kv_chunk: int = 1024,
    paged: Any | None = None,  # kernels.paged_attention.ops.PagedInfo
    collector: Collector = NULL_COLLECTOR,
) -> jax.Array:
    if paged is not None:
        # paged-KV decode: k/v are the *physical block pool* ``[num_blocks,
        # bs, K*D]`` and the kernel walks ``paged.tables`` instead of a
        # gathered dense view — S == 1, per-slot ``kv_len`` masks dead
        # positions.  (No ``attn_probs`` tag on this path: probabilities
        # never materialize outside the kernel.)
        from repro.kernels.paged_attention.ops import paged_attention

        return paged_attention(
            q, k, v, tables=paged.tables, kv_len=kv_len, scale=scale,
            window=window, impl=paged.impl, layer=paged.layer,
        )

    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // K
    qg = q.reshape(B, S, K, G, D)

    if S == 1 or impl == "naive" or T <= kv_chunk:
        s = jnp.einsum(
            "bskgd,btkd->bskgt", qg, k, preferred_element_type=jnp.float32
        ) * scale
        m = _mask(positions_q, jnp.arange(T), causal, window, kv_len)
        m = m.reshape((B if m.ndim == 3 else 1), S, 1, 1, T)
        s = jnp.where(m, s, BIG_NEG)
        p = jax.nn.softmax(s, axis=-1)
        p = collector.tag("attn_probs", p)
        o = jnp.einsum(
            "bskgt,btkd->bskgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return o.reshape(B, S, H, Dv).astype(q.dtype)

    if impl == "local_block" and window is not None and S == T and S % window == 0:
        return _local_block_attention(
            qg, k, v, scale=scale, window=window, collector=collector
        ).reshape(B, S, H, Dv).astype(q.dtype)

    if impl in ("pallas", "pallas_interpret") and kv_len is None:
        from repro.kernels.flash_attention.ops import flash_attention as fa

        return fa(q, k, v, scale=scale, causal=causal, window=window, impl=impl)

    # flash path: chunked online-softmax with a custom VJP that recomputes
    # scores in the backward pass (nothing quadratic is saved for bwd)
    pad = (-T) % kv_chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kv_len_arr = jnp.asarray(T if kv_len is None else kv_len, jnp.int32)
    f = _make_flash(float(scale), bool(causal), window, int(kv_chunk))
    o = f(qg, k, v, jnp.asarray(positions_q), kv_len_arr)
    return o.reshape(B, S, H, Dv).astype(q.dtype)


def _chunk_mask(positions_q, i, kv_chunk, causal, window, kv_len, B, S):
    pk = i * kv_chunk + jnp.arange(kv_chunk)
    msk = _mask(positions_q, pk, causal, window, kv_len)
    return msk.reshape((B if msk.ndim == 3 else 1), S, 1, 1, kv_chunk)


def _flash_forward(qg, k, v, pq, kv_len, scale, causal, window, kv_chunk):
    B, S, K, G, D = qg.shape
    Dv = v.shape[-1]
    nc = k.shape[1] // kv_chunk
    kc = jnp.moveaxis(k.reshape(B, nc, kv_chunk, K, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nc, kv_chunk, K, Dv), 1, 0)

    def body(carry, inp):
        m_r, l_r, o_r = carry
        i, kb, vb = inp
        s = jnp.einsum(
            "bskgd,bckd->bskgc", qg, kb, preferred_element_type=jnp.float32
        ) * scale
        msk = _chunk_mask(pq, i, kv_chunk, causal, window, kv_len, B, S)
        s = jnp.where(msk, s, BIG_NEG)
        m_new = jnp.maximum(m_r, s.max(-1))
        corr = jnp.exp(m_r - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_r * corr + p.sum(-1)
        o_new = o_r * corr[..., None] + jnp.einsum(
            "bskgc,bckd->bskgd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, o_new), None

    _q_axes = ("batch", "seq", "kv_heads_act", "heads_act", "head_dim_act")
    m0 = shard_act(jnp.full((B, S, K, G), BIG_NEG, jnp.float32), _q_axes[:-1])
    l0 = shard_act(jnp.zeros((B, S, K, G), jnp.float32), _q_axes[:-1])
    o0 = shard_act(jnp.zeros((B, S, K, G, Dv), jnp.float32), _q_axes)
    (m_f, l_f, o_f), _ = jax.lax.scan(body, (m0, l0, o0), (jnp.arange(nc), kc, vc))
    o = o_f / jnp.where(l_f[..., None] == 0, 1.0, l_f[..., None])
    lse = jnp.where(l_f == 0, 0.0, m_f + jnp.log(jnp.maximum(l_f, 1e-30)))
    return o, lse


import functools


@functools.lru_cache(maxsize=None)
def _make_flash(scale: float, causal: bool, window: int | None, kv_chunk: int):
    @jax.custom_vjp
    def flash(qg, k, v, pq, kv_len):
        o, _ = _flash_forward(qg, k, v, pq, kv_len, scale, causal, window, kv_chunk)
        return o

    def fwd(qg, k, v, pq, kv_len):
        o, lse = _flash_forward(qg, k, v, pq, kv_len, scale, causal, window, kv_chunk)
        return o, (qg, k, v, pq, kv_len, o, lse)

    def bwd(res, do):
        qg, k, v, pq, kv_len, o, lse = res
        B, S, K, G, D = qg.shape
        Dv = v.shape[-1]
        nc = k.shape[1] // kv_chunk
        kc = jnp.moveaxis(k.reshape(B, nc, kv_chunk, K, D), 1, 0)
        vc = jnp.moveaxis(v.reshape(B, nc, kv_chunk, K, Dv), 1, 0)
        # pin the cotangent to the forward activation sharding — without an
        # anchor GSPMD can lose it on multi-axis meshes and fall back to
        # "involuntary full rematerialization" (full replication)
        _q_axes = ("batch", "seq", "kv_heads_act", "heads_act", "head_dim_act")
        do = shard_act(do.astype(jnp.float32), _q_axes)
        delta = shard_act((do * o).sum(-1), _q_axes[:-1])  # [B,S,K,G]

        do_b = do.astype(k.dtype)

        def body(dq, inp):
            # matmul operands stay bf16 (f32 accumulation via preferred) —
            # keeping them f32 makes XLA hoist converts before the KV gathers,
            # doubling gather bytes
            i, kb, vb = inp
            s = jnp.einsum(
                "bskgd,bckd->bskgc", qg, kb, preferred_element_type=jnp.float32
            ) * scale
            msk = _chunk_mask(pq, i, kv_chunk, causal, window, kv_len, B, S)
            p = jnp.where(msk, jnp.exp(s - lse[..., None]), 0.0)
            p_b = p.astype(k.dtype)
            dv_c = jnp.einsum("bskgc,bskgv->bckv", p_b, do_b,
                              preferred_element_type=jnp.float32)
            dp = jnp.einsum("bskgv,bckv->bskgc", do_b, vb,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta[..., None]) * scale
            ds_b = ds.astype(k.dtype)
            dq = dq + jnp.einsum("bskgc,bckd->bskgd", ds_b, kb,
                                 preferred_element_type=jnp.float32)
            dq = shard_act(dq, _q_axes)
            dk_c = jnp.einsum("bskgc,bskgd->bckd", ds_b, qg,
                              preferred_element_type=jnp.float32)
            return dq, (dk_c.astype(k.dtype), dv_c.astype(v.dtype))

        dq0 = shard_act(jnp.zeros((B, S, K, G, D), jnp.float32), _q_axes)
        dq, (dk_s, dv_s) = jax.lax.scan(body, dq0, (jnp.arange(nc), kc, vc))
        dk = jnp.moveaxis(dk_s, 0, 1).reshape(B, nc * kv_chunk, K, D)
        dv = jnp.moveaxis(dv_s, 0, 1).reshape(B, nc * kv_chunk, K, Dv)
        return (
            dq.astype(qg.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None,
        )

    flash.defvjp(fwd, bwd)
    return flash


def _local_block_attention(
    qg: jax.Array,  # [B, S, K, G, D]
    k: jax.Array,  # [B, S, K, D]
    v: jax.Array,
    *,
    scale: float,
    window: int,
    collector: Collector = NULL_COLLECTOR,
) -> jax.Array:
    """Banded local attention: each W-block of queries attends to its own and
    the previous key block — linear cost in S (vs masked-quadratic chunked)."""
    B, S, K, G, D = qg.shape
    Dv = v.shape[-1]
    W = window
    nb = S // W
    qb = qg.reshape(B, nb, W, K, G, D)
    kb = k.reshape(B, nb, W, K, D)
    vb = v.reshape(B, nb, W, K, Dv)
    kprev = jnp.pad(kb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    vprev = jnp.pad(vb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    k2 = jnp.concatenate([kprev, kb], axis=2)  # [B, nb, 2W, K, D]
    v2 = jnp.concatenate([vprev, vb], axis=2)
    s = jnp.einsum(
        "bnwkgd,bnckd->bnwkgc", qb, k2, preferred_element_type=jnp.float32
    ) * scale
    # positions within the 2W strip: query i (at W+i), key j; attend iff
    # j <= W+i and j > i (window) and (block>0 or j >= W)
    i = jnp.arange(W)[:, None]
    j = jnp.arange(2 * W)[None, :]
    base = (j <= W + i) & (j > i)
    first = base & (j >= W)
    blk = jnp.arange(nb)[:, None, None]
    msk = jnp.where(blk > 0, base[None], first[None])  # [nb, W, 2W]
    s = jnp.where(msk[None, :, :, None, None, :], s, BIG_NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bnwkgc,bnckd->bnwkgd", p.astype(v2.dtype), v2,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, S, K, G, Dv)


# ---------------------------------------------------------------------------
# GQA attention block (projections + rope + cache)
# ---------------------------------------------------------------------------


def gqa_init(b: ParamBuilder, cfg: ModelConfig, window: int | None = None):
    D, H, K, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    b.param("wq", (D, H, dh), ("embed_w", "heads_w", "head_dim_w"), fan_in=D)
    b.param("wk", (D, K, dh), ("embed_w", "kv_heads_w", "head_dim_w"), fan_in=D)
    b.param("wv", (D, K, dh), ("embed_w", "kv_heads_w", "head_dim_w"), fan_in=D)
    b.param("wo", (H, dh, D), ("heads_w", "head_dim_w", "embed_w"),
            fan_in=H * dh, scale=1.0 / math.sqrt(2 * cfg.num_layers))
    if cfg.qkv_bias:
        b.param("bq", (H, dh), ("heads_w", "head_dim_w"), init="zeros")
        b.param("bk", (K, dh), ("kv_heads_w", "head_dim_w"), init="zeros")
        b.param("bv", (K, dh), ("kv_heads_w", "head_dim_w"), init="zeros")
    if cfg.qk_norm:
        b.param("q_norm", (dh,), ("head_dim_w",), init="ones")
        b.param("k_norm", (dh,), ("head_dim_w",), init="ones")


def gqa_apply(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,  # [B, S, D]
    *,
    positions: jax.Array,  # [S] or [B,S] absolute positions
    window: int | None = None,
    causal: bool = True,
    cache: dict | None = None,  # {"k","v"} [B, T, K, dh] ring/linear cache
    cache_pos: jax.Array | None = None,  # scalar write position, or [B] paged
    mrope_position_ids: jax.Array | None = None,  # [3, B, S]
    paged: Any | None = None,  # PagedInfo: cache leaves are pool blocks
    collector: Collector = NULL_COLLECTOR,
) -> tuple[jax.Array, dict | None]:
    B, S, D = x.shape
    H, K, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    kk = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    vv = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        kk = kk + p["bk"].astype(x.dtype)
        vv = vv + p["bv"].astype(x.dtype)
    mrope = cfg.mrope_sections and mrope_position_ids is not None
    if (
        cache is not None and paged is not None and paged.prefill
        and S > 1 and causal and not mrope and collector is NULL_COLLECTOR
    ):
        # fused flash-prefill: norm + rope + pool scatter + banded attention
        # in one op straight against the block pool — full prefill, chunked
        # prefill, and the spec-verify step all land here (decode S == 1
        # keeps the decode kernel below).  The raw q rides into the kernel,
        # whose prologue fuses the qk_norm/rope entry; the K side reuses the
        # jnp helpers so pool contents match this function's generic branch
        # bit-for-bit.  Gated off whenever a collector is live: the fused op
        # never materializes the roped q/k this function would tag.
        from repro.kernels.paged_attention.ops import paged_prefill

        o, new_cache = paged_prefill(
            q, kk, vv, cache["k"], cache["v"],
            tables=paged.tables, positions=positions,
            block_size=paged.block_size,
            scale=1.0 / math.sqrt(dh),
            window=window, impl=paged.impl, layer=paged.layer,
            q_norm=p["q_norm"] if cfg.qk_norm else None,
            k_norm=p["k_norm"] if cfg.qk_norm else None,
            eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
            q_start=paged.q_start,
        )
        out = jnp.einsum(
            "bshk,hkd->bsd", o.astype(x.dtype), p["wo"].astype(x.dtype)
        )
        return out, new_cache
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q, cfg.norm_eps)
        kk = rms_head_norm(p["k_norm"], kk, cfg.norm_eps)
    if mrope:
        q = apply_mrope(q, mrope_position_ids, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
    q = collector.tag("q", q)
    vv = collector.tag("v", vv)
    q = shard_act(q, ("batch", "seq", "heads_act", "head_dim_act"))

    kv_len = None
    new_cache = None
    if cache is not None and paged is not None:
        # paged decode: cache leaves are the physical pool ``[(n_layers,)
        # num_blocks, bs, K*dh]`` shared by all slots; the new tokens' K/V
        # go *straight into the blocks owning each slot's write positions*
        # (no dense gather, no block write-back).  S > 1 is the speculative-
        # decoding verify step: S = draft_len + 1 tokens land at consecutive
        # positions of the same slot.  Inactive slots sit at pos 0 of the
        # null block — their writes collide there harmlessly and are masked
        # by kv_len; write positions beyond the table's reach (padded verify
        # rows near a slot's max_len) are redirected to the null block too,
        # so clamped gathers can never corrupt a live block.  Values quantize
        # through bfloat16 (the lm attention-cache dtype) even when the pool
        # container is wider: XLA CPU cannot alias bfloat16 scatters, so such
        # pools store bf16 values in f32 so the in-place update actually
        # stays in place.
        kk = apply_rope(kk, positions, cfg.rope_theta)
        kk = collector.tag("k", kk)
        pos = positions                             # [B, S] write positions
        bs = paged.block_size
        in_reach = pos < paged.tables.shape[1] * bs
        blk = jnp.where(in_reach, pos // bs, 0)
        phys = jnp.take_along_axis(paged.tables, blk, axis=1)  # [B, S]
        phys = jnp.where(in_reach, phys, 0)
        off = pos % bs
        # [B, S, K*dh] rows: the pool stores a position's heads flattened
        k_new = kk.reshape(B, S, -1).astype(jnp.bfloat16)
        v_new = vv.reshape(B, S, -1).astype(jnp.bfloat16)
        k_new = k_new.astype(cache["k"].dtype)
        v_new = v_new.astype(cache["v"].dtype)
        if paged.layer is None:
            ck = cache["k"].at[phys, off].set(k_new)
            cv = cache["v"].at[phys, off].set(v_new)
        else:  # layer-stacked pools riding lm.forward's scan carry
            ck = cache["k"].at[paged.layer, phys, off].set(k_new)
            cv = cache["v"].at[paged.layer, phys, off].set(v_new)
        new_cache = {"k": ck, "v": cv}
        kf, vf = ck, cv
        kv_len = pos[:, -1] + 1                     # incl. all S new tokens
    elif cache is not None:
        # decode / cached path: rope the new K, write kv at cache_pos
        if mrope:
            kk = apply_mrope(kk, mrope_position_ids, cfg.mrope_sections, cfg.rope_theta)
        else:
            kk = apply_rope(kk, positions, cfg.rope_theta)
        kk = collector.tag("k", kk)
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], kk.astype(cache["k"].dtype), cache_pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vv.astype(cache["v"].dtype), cache_pos, axis=1)
        new_cache = {"k": ck, "v": cv}
        ck = shard_act(ck, ("batch", "kv_time", "kv_heads_act", "head_dim_act"))
        cv = shard_act(cv, ("batch", "kv_time", "kv_heads_act", "head_dim_act"))
        kf, vf = ck, cv
        kv_len = cache_pos + S
    else:
        # context-parallel path: gather K over the sequence axis while still
        # bf16 and *pre-rope* (rope's f32 internals would otherwise be hoisted
        # before the gather, doubling gather bytes), then rope locally.
        kf = shard_act(kk, ("batch", "seq_kv", "kv_heads_act", "head_dim_act"))
        vf = shard_act(vv, ("batch", "seq_kv", "kv_heads_act", "head_dim_act"))
        if mrope:
            kf = apply_mrope(kf, mrope_position_ids, cfg.mrope_sections, cfg.rope_theta)
        else:
            kf = apply_rope(kf, positions, cfg.rope_theta)
        kf = collector.tag("k", kf)

    # (windowed attention goes through the flash path: far chunks are fully
    # masked — wasted score FLOPs are <3% of model FLOPs even at 32k, and the
    # flash custom-VJP keeps memory flat, unlike the banded local_block path)
    impl = cfg.attn_impl
    if paged is not None:
        # pool leaves stay in cache dtype: casting here would materialize a
        # full pool-sized copy per layer — the kernel/ref upcasts only the
        # blocks it actually reads
        kf_a, vf_a = kf, vf
    else:
        kf_a, vf_a = kf.astype(x.dtype), vf.astype(x.dtype)
    o = attention(
        q.astype(x.dtype), kf_a, vf_a,
        scale=1.0 / math.sqrt(dh),
        positions_q=positions,
        causal=causal,
        window=window,
        kv_len=kv_len,
        impl=impl,
        kv_chunk=cfg.attn_kv_chunk,
        paged=paged,
        collector=collector,
    )
    o = collector.tag("attn_out", o)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_init(b: ParamBuilder, cfg: ModelConfig):
    m = cfg.mla
    D, H = cfg.d_model, cfg.num_heads
    dq = m.qk_nope_head_dim + m.qk_rope_head_dim
    b.param("wq", (D, H, dq), ("embed_w", "heads_w", "head_dim_w"), fan_in=D)
    b.param("wdkv", (D, m.kv_lora_rank), ("embed_w", "kv_lora_w"), fan_in=D)
    b.param("wkr", (D, m.qk_rope_head_dim), ("embed_w", "head_dim_w"), fan_in=D)
    b.param("kv_norm", (m.kv_lora_rank,), ("kv_lora_w",), init="ones")
    b.param("wuk", (m.kv_lora_rank, H, m.qk_nope_head_dim),
            ("kv_lora_w", "heads_w", "head_dim_w"), fan_in=m.kv_lora_rank)
    b.param("wuv", (m.kv_lora_rank, H, m.v_head_dim),
            ("kv_lora_w", "heads_w", "head_dim_w"), fan_in=m.kv_lora_rank)
    b.param("wo", (H, m.v_head_dim, D), ("heads_w", "head_dim_w", "embed_w"),
            fan_in=H * m.v_head_dim, scale=1.0 / math.sqrt(2 * cfg.num_layers))


def mla_cache_width(cfg: ModelConfig) -> int:
    """Width of one cached token of MLA: ``[ckv | k_pe]`` rounded up to a
    lane multiple (576 -> 640 for DeepSeek-V2), the row the latent kernel
    DMAs per block; the tail is zero."""
    m = cfg.mla
    return -(-(m.kv_lora_rank + m.qk_rope_head_dim) // 128) * 128


def _latent_row(cfg: ModelConfig, ckv: jax.Array, kpe: jax.Array) -> jax.Array:
    """``[ckv | k_pe | zeros]``: one cache row per token."""
    pad = mla_cache_width(cfg) - ckv.shape[-1] - kpe.shape[-1]
    zeros = jnp.zeros((*ckv.shape[:-1], pad), ckv.dtype)
    return jnp.concatenate([ckv, kpe.astype(ckv.dtype), zeros], axis=-1)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(qk_nope + qk_rope)^-0.5``, times ``mscale(factor, mscale_all_dim)^2``
    under YaRN (DeepSeek-V2's ``softmax_scale``)."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _mla_qkr(p, cfg, x, positions):
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    qn = q[..., : m.qk_nope_head_dim]
    qr = apply_rope(q[..., m.qk_nope_head_dim :], positions, cfg.rope_theta,
                    cfg.rope_scaling)
    return qn, qr


def _mla_expanded(p, cfg, qn, qr, ckv, kpe, positions, scale, collector):
    """Attention with K and V up-projected per head from the latent (the
    training and whole-prompt prefill form): q = [q_nope, q_pe], k =
    [ckv W_uk, k_pe] (k_pe shared by every head), v = ckv W_uv."""
    m = cfg.mla
    B, S, H, _ = qn.shape
    dt = qn.dtype
    kn = jnp.einsum("bsr,rhk->bshk", ckv, p["wuk"].astype(dt))
    vv = jnp.einsum("bsr,rhv->bshv", ckv, p["wuv"].astype(dt))
    k_full = jnp.concatenate(
        [kn, jnp.broadcast_to(kpe[:, :, None, :], (B, S, H, m.qk_rope_head_dim))],
        axis=-1,
    )
    q_full = jnp.concatenate([qn, qr], axis=-1)
    q_full = shard_act(q_full, ("batch", "seq", "heads_act", "head_dim_act"))
    k_full = shard_act(k_full, ("batch", "seq_kv", "heads_act", "head_dim_act"))
    vv = shard_act(vv, ("batch", "seq_kv", "heads_act", "head_dim_act"))
    o = attention(
        q_full, k_full, vv,
        scale=scale,
        positions_q=positions,
        causal=True,
        impl=cfg.attn_impl,
        kv_chunk=cfg.attn_kv_chunk,
        collector=collector,
    )
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(dt))


def _mla_paged(p, cfg, qn, qr, ckv, kpe, cache, positions, paged, scale,
               collector):
    """MLA against the latent paged pool ``latent`` ``[(n,) blocks, bs, W]``
    (rows ``[ckv | k_pe | zeros]``), which takes the new rows in place (the
    same block/offset scatter as ``gqa_apply``'s paged branch).  A decode step
    (one query per slot) attends in the latent space through the paged
    latent kernel: ``[q_nope W_uk | q_pe]`` scores against the rows, the
    context accumulates over ``ckv``, then ``W_uv`` and ``W_o``.  A
    whole-prompt prefill (``paged.prefill`` with ``q_start == 0``) attends
    expanded over the prompt itself, then leaves its rows in the pool."""
    m = cfg.mla
    S = qn.shape[1]
    dt = qn.dtype
    whole_prompt = paged.prefill and paged.q_start == 0
    if not whole_prompt and (S > 1 or paged.prefill):
        raise NotImplementedError(
            "the latent (MLA) pool serves one-token decode and whole-prompt "
            "prefill; chunked prefill and speculative verification would need "
            "a latent multi-query kernel")
    pos = positions                                  # [B, S] write positions
    bs = paged.block_size
    in_reach = pos < paged.tables.shape[1] * bs
    blk = jnp.where(in_reach, pos // bs, 0)
    phys = jnp.take_along_axis(paged.tables, blk, axis=1)
    phys = jnp.where(in_reach, phys, 0)
    off = pos % bs
    # rows quantize through bfloat16 (the cache dtype) even in a wider
    # container, as the GQA pool does
    pool = cache["latent"]
    row = _latent_row(cfg, ckv, kpe).astype(jnp.bfloat16).astype(pool.dtype)
    if paged.layer is None:
        pool = pool.at[phys, off].set(row)
    else:
        pool = pool.at[paged.layer, phys, off].set(row)
    if whole_prompt:
        out = _mla_expanded(p, cfg, qn, qr, ckv, kpe, positions, scale,
                            collector)
        return out, {"latent": pool}
    from repro.kernels.paged_attention.ops import paged_latent_attention

    q_lat = jnp.einsum("bhk,rhk->bhr", qn[:, 0], p["wuk"].astype(dt))
    q = _latent_row(cfg, q_lat, qr[:, 0])            # [B, H, W]
    ctx = paged_latent_attention(
        q, pool, tables=paged.tables, kv_len=pos[:, 0] + 1,
        r=m.kv_lora_rank, scale=scale, impl=paged.impl, layer=paged.layer,
    )                                                # [B, H, r] f32
    o = jnp.einsum("bhr,rhv->bhv", ctx.astype(dt), p["wuv"].astype(dt))
    out = jnp.einsum("bhv,hvd->bd", o, p["wo"].astype(dt))
    return out[:, None], {"latent": pool}


def mla_apply(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    positions: jax.Array,
    cache: dict | None = None,  # {"latent": [B, T, W]} rows, or the pool
    cache_pos: jax.Array | None = None,
    paged: Any | None = None,  # PagedInfo: cache leaves are latent pools
    collector: Collector = NULL_COLLECTOR,
) -> tuple[jax.Array, dict | None]:
    m = cfg.mla
    S = x.shape[1]
    scale = mla_softmax_scale(cfg)
    qn, qr = _mla_qkr(p, cfg, x, positions)
    ckv = jnp.einsum("bsd,dr->bsr", x, p["wdkv"].astype(x.dtype))
    ckv = norm_apply({"scale": p["kv_norm"]}, ckv, "rmsnorm", cfg.norm_eps)
    kpe = apply_rope(
        jnp.einsum("bsd,dr->bsr", x, p["wkr"].astype(x.dtype)), positions,
        cfg.rope_theta, cfg.rope_scaling,
    )
    if paged is not None:
        return _mla_paged(p, cfg, qn, qr, ckv, kpe, cache, positions, paged,
                          scale, collector)

    if cache is not None and S == 1:
        # absorbed decode: attend in the latent space (compressed KV cache)
        lat = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], _latent_row(cfg, ckv, kpe).astype(
                cache["latent"].dtype), cache_pos, axis=1)
        new_cache = {"latent": lat}
        lat = shard_act(lat, ("batch", "kv_time", "kv_lora_act"))
        r = m.kv_lora_rank
        ckv_s = lat[..., :r]
        kpe_s = lat[..., r:r + m.qk_rope_head_dim]
        T = lat.shape[1]
        q_lat = jnp.einsum("bshk,rhk->bshr", qn, p["wuk"].astype(x.dtype))
        s = (
            jnp.einsum("bshr,btr->bsht", q_lat, ckv_s.astype(x.dtype),
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bshk,btk->bsht", qr, kpe_s.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        ) * scale
        kv_len = cache_pos + 1
        msk = jnp.arange(T)[None, None, None, :] < kv_len
        s = jnp.where(msk, s, BIG_NEG)
        prob = jax.nn.softmax(s, axis=-1)
        prob = collector.tag("attn_probs", prob)
        ctx = jnp.einsum("bsht,btr->bshr", prob.astype(x.dtype), ckv_s.astype(x.dtype),
                         preferred_element_type=jnp.float32).astype(x.dtype)
        o = jnp.einsum("bshr,rhv->bshv", ctx, p["wuv"].astype(x.dtype))
        out = jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(x.dtype))
        return out, new_cache

    # full (training / prefill) path
    out = _mla_expanded(p, cfg, qn, qr, ckv, kpe, positions, scale, collector)
    new_cache = None
    if cache is not None:  # prefill fills the compressed cache
        lat = cache["latent"]
        row = _latent_row(cfg, ckv, kpe).astype(lat.dtype)
        new_cache = {"latent": jnp.pad(
            row, [(0, 0), (0, lat.shape[1] - S), (0, 0)])}
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(b: ParamBuilder, cfg: ModelConfig, d_ff: int | None = None):
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    scale_out = 1.0 / math.sqrt(2 * cfg.num_layers)
    if cfg.mlp_kind in ("swiglu", "geglu"):
        b.param("w_gate", (D, F), ("embed_w", "mlp_w"), fan_in=D)
    b.param("w_up", (D, F), ("embed_w", "mlp_w"), fan_in=D)
    b.param("w_down", (F, D), ("mlp_w", "embed_w"), fan_in=F, scale=scale_out)


def mlp_apply(p: dict, cfg: ModelConfig, x: jax.Array,
              collector: Collector = NULL_COLLECTOR) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    if cfg.mlp_kind == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
        h = jax.nn.silu(g) * h
    elif cfg.mlp_kind == "geglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
        h = jax.nn.gelu(g) * h
    elif cfg.mlp_kind == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(cfg.mlp_kind)
    h = shard_act(h, ("batch", "seq_act", "mlp_act"))
    h = collector.tag("mlp_hidden", h)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Mixture of Experts (sort/scatter dispatch — no one-hot einsum FLOPs)
# ---------------------------------------------------------------------------


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_init(b: ParamBuilder, cfg: ModelConfig):
    """The router keeps all ``num_experts`` outputs; the expert weights are
    the ``held`` experts this device computes (``MoEConfig.experts_held``,
    all of them unless set)."""
    D = cfg.d_model
    mo = cfg.moe
    E, F = mo.held, mo.expert_d_ff
    b.param("router", (D, mo.num_experts), ("embed_w", None), fan_in=D)
    b.param("w_gate", (E, D, F), ("expert_w", "embed_w", "expert_mlp"), fan_in=D)
    b.param("w_up", (E, D, F), ("expert_w", "embed_w", "expert_mlp"), fan_in=D)
    b.param("w_down", (E, F, D), ("expert_w", "expert_mlp", "embed_w"),
            fan_in=F, scale=1.0 / math.sqrt(2 * cfg.num_layers))
    if mo.num_shared_experts:
        s = b.sub("shared")
        shared_cfg = cfg.replace(mlp_kind="swiglu")
        mlp_init(s, shared_cfg, d_ff=mo.num_shared_experts * F)


def _route(p: dict, cfg: ModelConfig, x: jax.Array):
    """Router over all ``num_experts``: float32 logits, softmax, greedy
    top-k; gates renormalised only where ``norm_topk_prob``, then scaled by
    ``routed_scaling_factor``.  Returns (logits, probs, gate, expert ids)."""
    mo = cfg.moe
    logits = jnp.einsum(
        "...d,de->...e", x.astype(jnp.float32),
        p["router"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, mo.top_k)
    if mo.norm_topk_prob:
        gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
    if mo.routed_scaling_factor != 1.0:
        gate = gate * mo.routed_scaling_factor
    return logits, probs, gate, eidx


def _router_aux(mo, logits, probs, eidx):
    """Switch-style load-balance loss and router z-loss over every token."""
    E = mo.num_experts
    me = probs.reshape(-1, E).mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / eidx.size
    aux_lb = (me * ce).sum() * E * mo.router_aux_coef
    aux_z = jnp.square(jax.nn.logsumexp(logits, axis=-1)).mean() * mo.router_z_coef
    return aux_lb + aux_z


def _shared_experts(p: dict, x: jax.Array) -> jax.Array:
    """The shared experts: a plain SwiGLU that every token passes through."""
    hs = jnp.einsum("...d,df->...f", x, p["w_up"].astype(x.dtype))
    gs = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(x.dtype))
    return jnp.einsum("...f,fd->...d", jax.nn.silu(gs) * hs,
                      p["w_down"].astype(x.dtype))


def expert_parallel_degree(num_experts: int) -> int:
    """How many devices the installed mesh spreads the experts over (the mesh
    axes the ``expert_act`` rule gives an expert axis of ``num_experts``)."""
    from repro.parallel.sharding import current_mesh_and_rules, logical_to_spec

    mesh, rules = current_mesh_and_rules()
    if mesh is None:
        return 1
    spec = logical_to_spec(("expert_act",), (num_experts,), mesh, rules)
    axes = spec[0] if len(spec) else None
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else axes
    return math.prod(mesh.shape[a] for a in axes)


def moe_apply(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,  # [B, S, D]
    *,
    n_seq_groups: int = 1,
    collector: Collector = NULL_COLLECTOR,
    layer: jax.Array | None = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Routed experts plus shared experts.

    With ``layer`` set, the routed experts' weights (``EXPERT_WEIGHTS``) come
    stacked over the segment's layers and this is layer ``layer`` of them
    (``lm.forward`` passes them so: the grouped kernel reads its layer in
    place, where a per-layer slice would be copied on every step).

    Where the mesh spreads the experts over several devices the layer keeps
    the capacity dispatch and its all-to-all (``_moe_capacity``).  Otherwise
    it is one device's share of an expert-parallel layer, dropless: it holds
    the experts ``[expert_offset, expert_offset + experts_held)`` (all of
    them unless ``MoEConfig`` says otherwise), routes over all
    ``num_experts``, sorts the picks that land on its experts and runs them
    through one grouped matmul (``kernels.moe.expert_ffn``).  Picks on
    experts it does not hold add nothing here (another device computes
    them); the shared experts are added once.  A token's result never
    depends on what else shares the batch.
    """
    mo = cfg.moe
    if expert_parallel_degree(mo.num_experts) > 1:
        if layer is not None:
            p = {**p, **{k: p[k][layer] for k in EXPERT_WEIGHTS}}
        return _moe_capacity(p, cfg, x, n_seq_groups=n_seq_groups,
                             collector=collector)
    from repro.kernels.moe import expert_ffn

    B, S, D = x.shape
    K, Eh = mo.top_k, mo.held
    xt = x.reshape(B * S, D)
    logits, probs, gate, eidx = _route(p, cfg, xt)     # [T, K]
    gate = collector.tag("router_gate", gate)
    local = eidx - mo.expert_offset
    held = (local >= 0) & (local < Eh)
    key = jnp.where(held, local, Eh).reshape(-1)        # [T*K]; absent last
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((Eh + 1,), jnp.int32).at[key].add(1)[:Eh]
    xs = jnp.take(xt, order // K, axis=0)               # picks, held first
    ys = expert_ffn(xs, p["w_gate"], p["w_up"], p["w_down"], counts,
                    layer=layer)
    back = jnp.argsort(order)                           # pick -> sorted row
    yk = jnp.take(ys, back, axis=0).reshape(B * S, K, D)
    # a select, not a product: rows past the held picks are never written
    w = jnp.where(held[..., None], gate[..., None] * yk.astype(jnp.float32), 0)
    y = w.sum(1).astype(x.dtype)
    if mo.num_shared_experts:
        y = y + _shared_experts(p["shared"], xt)
    aux = {
        "moe_aux_loss": _router_aux(mo, logits, probs, eidx),
        "moe_drop_frac": jnp.zeros((), jnp.float32),
        "moe_held_picks": counts,
    }
    return y.reshape(B, S, D), aux


def _moe_capacity(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,  # [B, S, D]
    *,
    n_seq_groups: int = 1,
    collector: Collector = NULL_COLLECTOR,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Top-k routed experts with capacity, sort-based dispatch, EP all-to-all.

    Tokens are viewed as [G, C, D] groups (G = batch x seq-chunks, matching the
    activation sharding so dispatch is local); expert compute is sharded over
    ``expert_w``; the G->E resharding between constraints is the all-to-all.
    """
    mo = cfg.moe
    B, S, D = x.shape
    E, K = mo.num_experts, mo.top_k
    nsg = n_seq_groups if S % max(n_seq_groups, 1) == 0 else 1
    Cg = S // nsg
    G = B * nsg
    N = Cg * K
    # Regroup tokens so each group is device-local *before* any data-dependent
    # gather/sort — GSPMD cannot keep gathers over a sharded seq dim sharded.
    # The reshape is staged through an explicitly-anchored 4-D intermediate:
    # propagating the merged [G] sharding straight through the reshape lets
    # Shardy assign B a greedy (data+model) sharding that conflicts with the
    # residual layout and degenerates into full rematerialization.
    x4 = shard_act(
        x.reshape(B, nsg, Cg, D), ("batch", "seq_act", None, "embed_act")
    )
    xt = shard_act(x4.reshape(G, Cg, D), ("moe_groups", None, "embed_act"))

    logits, probs, gate, eidx = _route(p, cfg, xt)     # [G, Cg, K]
    gate = collector.tag("router_gate", gate)
    aux_loss = _router_aux(mo, logits, probs, eidx)

    cap = max(int(math.ceil(Cg * K / E * mo.capacity_factor)), 1)

    # ---- sort-based dispatch (no one-hot einsum FLOPs, no [G,N,D] tensors):
    # build a slot->token index table, then one output-sized gather.
    flat_e = eidx.reshape(G, N)
    order = jnp.argsort(flat_e, axis=-1, stable=True)  # [G, N] sorted entries
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    first = jax.vmap(lambda s: jnp.searchsorted(s, jnp.arange(E + 1)))(sorted_e)
    # slot (e, c) holds sorted entry j = first[e] + c while j < first[e+1]
    j = first[:, :E, None] + jnp.arange(cap)[None, None, :]  # [G, E, cap]
    valid = j < first[:, 1:, None]
    tok_sorted = order // K  # token of each sorted entry
    tok_for_slot = jnp.where(
        valid,
        jnp.take_along_axis(tok_sorted, jnp.minimum(j, N - 1).reshape(G, E * cap), axis=-1
                            ).reshape(G, E, cap),
        Cg,  # sentinel -> zero pad row
    )
    xt_pad = jnp.pad(xt, ((0, 0), (0, 1), (0, 0)))
    expert_in = jnp.take_along_axis(
        xt_pad, tok_for_slot.reshape(G, E * cap)[..., None], axis=1
    ).reshape(G, E, cap, D)
    expert_in = shard_act(expert_in, ("moe_groups", "expert_pre", "moe_cap", "embed_act"))
    # all-to-all: groups spread back over the data axes, experts onto EP axis
    expert_in = shard_act(expert_in, ("moe_groups_post", "expert_act", "moe_cap", "embed_act"))

    h_up = jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"].astype(x.dtype))
    h_g = jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"].astype(x.dtype))
    h = jax.nn.silu(h_g) * h_up
    expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(x.dtype))
    expert_out = shard_act(expert_out, ("moe_groups_post", "expert_act", "moe_cap", "embed_act"))
    # reverse all-to-all
    expert_out = shard_act(expert_out, ("moe_groups", "expert_pre", "moe_cap", "embed_act"))
    flat_out = jnp.pad(
        expert_out.reshape(G, E * cap, D), ((0, 0), (0, 1), (0, 0))
    )  # zero row at E*cap for dropped entries

    # ---- combine: per top-k choice, gather the slot output and weight it
    inv = jnp.argsort(order, axis=-1, stable=True)  # entry -> sorted position
    slot_sorted = jnp.arange(N)[None, :] - jnp.take_along_axis(first[:, :E], sorted_e, -1)
    dest_sorted = jnp.where(
        slot_sorted < cap, sorted_e * cap + slot_sorted, E * cap
    )
    slot_entry = jnp.take_along_axis(dest_sorted, inv, axis=-1)  # [G, N]
    y = jnp.zeros((G, Cg, D), x.dtype)
    for k in range(K):
        se = slot_entry[:, k::K]  # [G, Cg] entries (t, k) are laid out t*K+k
        out_k = jnp.take_along_axis(flat_out, se[..., None], axis=1)
        y = y + out_k * gate[:, :, k][..., None].astype(x.dtype)

    if mo.num_shared_experts:
        # shared experts: a plain SwiGLU applied in the group-local layout
        y = y + _shared_experts(p["shared"], xt)

    y = shard_act(y, ("moe_groups", None, "embed_act"))
    y4 = shard_act(
        y.reshape(B, nsg, Cg, D), ("batch", "seq_act", None, "embed_act")
    )
    aux = {
        "moe_aux_loss": aux_loss,
        "moe_drop_frac": (slot_entry == E * cap).mean(),
    }
    return y4.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# Embedding + vocab-sharded chunked cross-entropy
# ---------------------------------------------------------------------------


def embed_init(b: ParamBuilder, cfg: ModelConfig):
    # vocab rows padded to a shardable multiple (Megatron-style); padded
    # logits are masked out in logits_fn / chunked_xent
    b.param("embedding", (cfg.padded_vocab, cfg.d_model), ("vocab_w", "embed_w"),
            fan_in=cfg.d_model, scale=1.0)
    if not cfg.tie_embeddings:
        b.param("unembed", (cfg.d_model, cfg.padded_vocab), ("embed_w", "vocab_w"),
                fan_in=cfg.d_model)


def embed_apply(p: dict, cfg: ModelConfig, tokens: jax.Array, dtype) -> jax.Array:
    emb = p["embedding"].astype(dtype)
    x = jnp.take(emb, tokens, axis=0)
    if cfg.scale_emb != 1.0:
        x = x * cfg.scale_emb
    return shard_act(x, ("batch", "seq_act", "embed_act"))


def _unembed_matrix(p: dict, cfg: ModelConfig, dtype) -> jax.Array:
    if cfg.tie_embeddings:
        return p["embedding"].astype(dtype).T
    return p["unembed"].astype(dtype)


def _mask_padded_vocab(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    col = jnp.arange(cfg.padded_vocab)
    return jnp.where(col < cfg.vocab_size, logits, BIG_NEG)


def logits_fn(p: dict, cfg: ModelConfig, y: jax.Array) -> jax.Array:
    """Full logits (serving path): y [B, S, D] -> [B, S, padded_V] with padded
    columns masked to -inf."""
    w = _unembed_matrix(p, cfg, y.dtype)
    if cfg.dim_model_base:
        y = y / (cfg.d_model / cfg.dim_model_base)
    logits = jnp.einsum("bsd,dv->bsv", y, w)
    logits = _mask_padded_vocab(cfg, logits)
    return shard_act(logits, ("ce_batch", "seq_ce", "vocab_act"))


def chunked_xent(
    p: dict,
    cfg: ModelConfig,
    y: jax.Array,  # [B, S, D] final hidden states
    targets: jax.Array,  # [B, S] int32
    loss_mask: jax.Array | None = None,  # [B, S]
) -> tuple[jax.Array, jax.Array]:
    """Sequence-chunked, vocab-sharded cross entropy; never materializes
    [B, S, V].  Custom VJP: logits are recomputed per chunk in backward with
    the analytic softmax gradient, and dy/dw leave in bf16 — grad reductions
    across the mesh run at half the bytes of the autodiff (f32) path.
    Returns (sum_loss, sum_count)."""
    B, S, D = y.shape
    w = _unembed_matrix(p, cfg, y.dtype)
    if cfg.dim_model_base:
        y = y / (cfg.d_model / cfg.dim_model_base)
    # regroup: batch over all data axes, sequence gathered, for clean chunking
    y = shard_act(y, ("ce_batch", "seq_ce", "embed_act"))
    c = min(cfg.logits_chunk, S)
    nchunks = max(S // c, 1)
    c = S // nchunks
    mask = (
        loss_mask.astype(jnp.float32)
        if loss_mask is not None
        else jnp.ones((B, S), jnp.float32)
    )
    fn = _make_ce(nchunks, c, cfg.vocab_size, cfg.padded_vocab)
    return fn(y, w, targets, mask)


@functools.lru_cache(maxsize=None)
def _make_ce(nchunks: int, c: int, vocab_real: int, padded: int):
    col_valid = None  # built lazily inside traces

    def _logits(yc, w):
        logits = jnp.einsum("bsd,dv->bsv", yc, w, preferred_element_type=jnp.float32)
        if padded != vocab_real:
            logits = jnp.where(jnp.arange(padded) < vocab_real, logits, BIG_NEG)
        return shard_act(logits, ("ce_batch", "seq_ce", "vocab_act"))

    def _forward(y, w, t, m):
        total = jnp.zeros((), jnp.float32)
        for i in range(nchunks):
            sl = slice(i * c, (i + 1) * c)
            logits = _logits(y[:, sl], w)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, t[:, sl, None], axis=-1)[..., 0]
            total = total + ((lse - tgt) * m[:, sl]).sum()
        return total, m.sum()

    @jax.custom_vjp
    def ce(y, w, t, m):
        return _forward(y, w, t, m)

    def fwd(y, w, t, m):
        return _forward(y, w, t, m), (y, w, t, m)

    def bwd(res, ct):
        y, w, t, m = res
        g = ct[0].astype(jnp.float32)  # cotangent of sum_loss
        dy_chunks = []
        dw = None
        for i in range(nchunks):
            sl = slice(i * c, (i + 1) * c)
            yc = y[:, sl]
            logits = _logits(yc, w)
            prob = jax.nn.softmax(logits, axis=-1)
            eq = jnp.arange(padded)[None, None, :] == t[:, sl, None]
            dlog = (prob - eq.astype(jnp.float32)) * (m[:, sl] * g)[..., None]
            dlog = dlog.astype(w.dtype)  # bf16 grad reductions
            dy_chunks.append(
                jnp.einsum("bcv,dv->bcd", dlog, w, preferred_element_type=jnp.float32)
                .astype(y.dtype)
            )
            dw_c = jnp.einsum("bcd,bcv->dv", yc, dlog,
                              preferred_element_type=jnp.float32)
            dw = dw_c if dw is None else dw + dw_c
        dy = jnp.concatenate(dy_chunks, axis=1)
        dy = shard_act(dy, ("ce_batch", "seq_ce", "embed_act"))
        return dy, dw.astype(w.dtype), None, None

    ce.defvjp(fwd, bwd)
    return ce
