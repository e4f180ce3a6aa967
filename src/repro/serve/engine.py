"""Serving step factories + cache sharding axes.

``serve_step`` semantics for the dry-run cells: ``decode_*`` / ``long_*``
lower one new token against a KV cache of ``seq_len`` (assignment spec);
``prefill_*`` lowers the full-prompt cache-fill.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import get_model
from repro.models.hooks import Collector, NULL_COLLECTOR

# cache leaf name -> logical axes (by trailing dims; leading "layers" handled
# by rank: stacked leaves carry one extra leading dim)
_CACHE_AXES: dict[str, tuple[str | None, ...]] = {
    "k": ("batch", "kv_time", "kv_heads_act", "head_dim_act"),
    "v": ("batch", "kv_time", "kv_heads_act", "head_dim_act"),
    "ck": ("batch", "kv_time", "kv_heads_act", "head_dim_act"),
    "cv": ("batch", "kv_time", "kv_heads_act", "head_dim_act"),
    "latent": ("batch", "kv_time", "kv_lora_act"),   # MLA [ckv | k_pe]
    "wkv": ("batch", "heads_act", "state", "state"),
    "x_prev": ("batch", "embed_act"),
    "conv": ("batch", "conv", "mlp_act"),
    "h": ("batch", "mlp_act"),
}


def cache_axes(cache: Any) -> Any:
    """Mirror a cache pytree with logical-axes tuples derived from leaf names."""

    def leaf_axes(path, leaf):
        name = None
        for p in reversed(path):
            if hasattr(p, "key"):
                name = p.key
                break
        axes = _CACHE_AXES[name]
        extra = leaf.ndim - len(axes)
        assert extra >= 0, (name, leaf.shape)
        return ("layers",) * extra + axes

    return jax.tree_util.tree_map_with_path(leaf_axes, cache)


def make_prefill_step(cfg: ModelConfig, collector: Collector = NULL_COLLECTOR) -> Callable:
    model = get_model(cfg)

    def prefill_step(params, batch, cache):
        return model.prefill(cfg, params, batch, cache, collector)

    return prefill_step


def make_decode_step(
    cfg: ModelConfig,
    collector: Collector = NULL_COLLECTOR,
    *,
    temperature: float = 0.0,
) -> Callable:
    model = get_model(cfg)
    from repro.serve.sampler import sample

    def decode_step(params, cache, tokens, pos):
        cache, logits = model.decode_step(cfg, params, cache, tokens, pos, collector)
        next_tok = sample(logits, temperature=temperature)
        return cache, logits, next_tok

    return decode_step


# ---------------------------------------------------------------------------
# MegaServe continuous-batching steps (per-slot positions)
# ---------------------------------------------------------------------------
#
# The static decode step above shares one scalar ``pos`` across the batch —
# fine when every slot decodes in lockstep, useless for continuous batching
# where each slot sits at its own length.  These factories vmap a B=1 decode
# over the slot axis so every lane carries its own cache position, without
# touching the model code.  Cache leaves follow the ``lm.init_cache`` layout
# ``[n_layers, batch, ...]`` (batch axis 1), hence ``in_axes=1``.


def make_paged_decode_step(
    cfg: ModelConfig,
    collector: Collector = NULL_COLLECTOR,
    *,
    block_size: int,
    paged_flags: Any,
    impl: str = "auto",
) -> Callable:
    """Returns ``step(params, pool, tables [S, M], tokens [S], pos [S]) ->
    (pool, logits [S, V], captures)`` — one batched decode over all slots
    straight against the physical block pool.  A model with a dropless
    expert layer also returns its routing picks per held expert in
    ``captures["moe_held_picks"]`` (``[n_moe_layers, experts_held]``), which
    the server's tick takes out before the captures reach a stream.

    MLA models decode through the latent pool and the paged latent kernel
    (``layers.mla_apply``); GQA models through the paged GQA kernel.

    Replaces the gathered path's ``gather -> vmap(B=1) -> scatter_decode``
    round trip: slots ride the batch axis of a single ``lm.forward`` call
    with per-slot positions, attention leaves dispatch to the paged-attention
    kernel (in-place pool block writes, block-table walk, O(kv_len) traffic),
    and slot-state leaves (rwkv/griffin recurrent state) use their dense
    per-slot pool storage directly.  ``paged_flags`` is the pool's leaf-kind
    tree (``PagedKVCache.paged``): pool leaves thread ``lm.forward``'s scan
    carry and are updated in place (donate the pool at the jit boundary!),
    so per-step cost is O(live kv_len), not O(pool).  ``tables`` may be
    sliced to the live block high-water mark; each distinct width compiles
    once.

    Captures surface with the slot axis leading (batched, not vmap-stacked),
    so per-position probe *reductions* see all slots at once — deep MegaScope
    probing should prefer the gathered path (``decode_path="gathered"``).
    """
    if cfg.input_kind != "tokens":
        raise ValueError(f"{cfg.name}: continuous batching serves token archs")
    from repro.kernels.paged_attention.ops import PagedInfo
    from repro.models import layers as L
    from repro.models import lm

    def step(params, pool, tables, tokens, pos):
        paged = PagedInfo(tables=tables, block_size=block_size, impl=impl)
        hidden, new_pool, aux = lm.forward(
            cfg, params, {"tokens": tokens[:, None]},
            cache=pool, cache_pos=pos, paged=paged,
            paged_flags=paged_flags, collector=collector,
        )
        logits = L.logits_fn(params, cfg, hidden)[:, 0]
        caps = dict(aux.get("captures", {}))
        if "moe_held_picks" in aux:
            caps["moe_held_picks"] = aux["moe_held_picks"]
        return new_pool, logits, caps

    return step


def make_spec_verify_step(
    cfg: ModelConfig,
    collector: Collector = NULL_COLLECTOR,
    *,
    block_size: int,
    paged_flags: Any,
    impl: str = "auto",
) -> Callable:
    """Returns ``step(params, pool, tables [S, M], tokens [S, Q], pos [S]) ->
    (pool, greedy [S, Q], logits [S, Q, V], captures)`` — the speculative-
    decoding verification forward: every slot scores Q = draft_len + 1 tokens
    (its current last token followed by the drafter's proposal, right-padded
    to the static Q) in ONE batched call against the physical block pool.

    Row ``i`` of ``logits``/``greedy`` is the target model's prediction for
    the position *after* ``tokens[:, i]``, so row ``i`` verifies draft token
    ``i + 1`` and the last accepted row supplies the bonus/correction token
    (see ``sampler.greedy_verify`` / ``sampler.rejection_verify``).  K/V for
    all Q tokens are written in place at ``pos + i``; the caller commits the
    accepted prefix by advancing the slot cursor and *rewinds* the rest —
    rejected writes sit beyond the new ``kv_len``, where every later read
    masks them and every later write overwrites them before they could ever
    become live (``Scheduler.trim_blocks``).

    ``Q`` is baked into the compiled executable (one compile per distinct
    draft length ceiling); padded rows beyond a slot's real draft cost
    compute but are causally masked for the rows that matter and their
    writes land in the null block once past the slot's grown table reach.
    """
    if cfg.input_kind != "tokens":
        raise ValueError(f"{cfg.name}: continuous batching serves token archs")
    if cfg.use_mla:
        raise ValueError(
            f"{cfg.name}: speculative verification scores several queries "
            "per slot, and the latent (MLA) pool has a one-query decode "
            "kernel only")
    from repro.kernels.paged_attention.ops import PagedInfo
    from repro.models import layers as L
    from repro.models import lm

    def step(params, pool, tables, tokens, pos):
        # prefill=True: the Q verify rows take the fused flash-prefill path
        # (q-block x kv-block kernel) instead of the generic dense branch
        paged = PagedInfo(
            tables=tables, block_size=block_size, impl=impl, prefill=True,
        )
        hidden, new_pool, aux = lm.forward(
            cfg, params, {"tokens": tokens},
            cache=pool, cache_pos=pos, paged=paged,
            paged_flags=paged_flags, collector=collector,
        )
        logits = L.logits_fn(params, cfg, hidden)          # [S, Q, V]
        return new_pool, jnp.argmax(logits, -1), logits, aux.get("captures", {})

    return step


def make_chunk_prefill_step(
    cfg: ModelConfig,
    collector: Collector = NULL_COLLECTOR,
    *,
    block_size: int,
    paged_flags: Any,
    impl: str = "auto",
) -> Callable:
    """Returns ``step(params, pool, tables [1, M], tokens [1, C], pos [1],
    n_last) -> (pool, last_logits [V], captures)`` — one fixed-size prompt
    chunk pushed through the q_len>1 paged kernel path straight into the
    slot's pool blocks.

    Chunk ``i`` writes K/V for positions ``pos .. pos+C-1`` of the owning
    slot and attends causally over everything already in the table (the
    same masking the spec-verify step relies on), so a long prompt becomes
    ``ceil(P / C)`` cheap calls with decode ticks interleaved between them
    instead of one monolithic stall.  ``n_last`` is the in-chunk index of
    the prompt's final real token — only the last chunk's logits (sliced
    there) are meaningful; earlier chunks' are discarded by the caller.
    Pad tokens past ``n_last`` on the final chunk write garbage K/V beyond
    the slot's ``kv_len``, where every later read masks them and the first
    decode write overwrites them.  ``C`` is baked into the compiled
    executable: one compile per (chunk_len, table width) pair.
    """
    if cfg.input_kind != "tokens":
        raise ValueError(f"{cfg.name}: continuous batching serves token archs")
    if cfg.use_mla:
        raise ValueError(
            f"{cfg.name}: a prompt chunk attends over the slot's earlier "
            "chunks in the pool, and the latent (MLA) pool has no multi-query "
            "kernel; MLA prefills whole prompts")
    from repro.kernels.paged_attention.ops import PagedInfo
    from repro.models import layers as L
    from repro.models import lm

    def step(params, pool, tables, tokens, pos, n_last):
        # prefill=True: the C chunk rows take the fused flash-prefill path
        paged = PagedInfo(
            tables=tables, block_size=block_size, impl=impl, prefill=True,
        )
        hidden, new_pool, aux = lm.forward(
            cfg, params, {"tokens": tokens},
            cache=pool, cache_pos=pos, paged=paged,
            paged_flags=paged_flags, collector=collector,
        )
        last = jax.lax.dynamic_slice_in_dim(hidden, n_last, 1, axis=1)
        logits = L.logits_fn(params, cfg, last)[0, 0]
        return new_pool, logits, aux.get("captures", {})

    return step


def make_flash_prefill_step(
    cfg: ModelConfig,
    collector: Collector = NULL_COLLECTOR,
    *,
    block_size: int,
    paged_flags: Any,
    impl: str = "auto",
) -> Callable:
    """Returns ``step(params, pool, tables [1, M], tokens [1, P], n_real) ->
    (pool, last_logits [V], captures)`` — the whole (right-padded) prompt in
    one call *straight into the slot's pool blocks* via the flash-prefill
    kernel: no dense ``[1, P, ...]`` cache is ever materialized and no
    ``scatter_prefill`` copy follows.

    ``q_start=0`` pins query 0 at absolute position 0 (statically, for the
    whole bucket), which unlocks the causal lower-triangular band in the
    kernel/oracle: prefill attention cost is ~P²/2 score work instead of the
    dense path's P² plus a pool-sized gather/scatter round trip.  Pad tokens
    past ``n_real`` write garbage K/V beyond the slot's ``kv_len`` exactly
    like the dense path's pad positions — masked by every later read,
    overwritten by the first decode write.  ``P`` (and the table width
    ``M = P / block_size``) is baked into the executable: one compile per
    pow2 bucket, same ladder the dense path uses.  MLA models attend
    expanded over the prompt and leave its latent rows in the pool
    (``layers.mla_apply``).
    """
    if cfg.input_kind != "tokens":
        raise ValueError(f"{cfg.name}: continuous batching serves token archs")
    from repro.kernels.paged_attention.ops import PagedInfo
    from repro.models import layers as L
    from repro.models import lm

    def step(params, pool, tables, tokens, n_real):
        paged = PagedInfo(
            tables=tables, block_size=block_size, impl=impl,
            prefill=True, q_start=0,
        )
        pos = jnp.zeros((1,), jnp.int32)
        hidden, new_pool, aux = lm.forward(
            cfg, params, {"tokens": tokens},
            cache=pool, cache_pos=pos, paged=paged,
            paged_flags=paged_flags, collector=collector,
        )
        last = jax.lax.dynamic_slice_in_dim(hidden, n_real - 1, 1, axis=1)
        logits = L.logits_fn(params, cfg, last)[0, 0]
        return new_pool, logits, aux.get("captures", {})

    return step


def make_seg_prefill(cfg: ModelConfig, collector: Collector = NULL_COLLECTOR) -> Callable:
    """Returns ``seg(params, cache, tokens [1, W], pos) -> (cache, last_logits
    [V], captures)`` — one exact-length prompt *segment* integrated into a
    dense cache at offset ``pos``, for recurrent-state families (rwkv /
    griffin) whose prefill must visit every real position.

    The caller splits ``n_real`` into its descending binary decomposition
    (13 -> 8 + 4 + 1) and runs one segment per power of two, carrying the
    cache between calls: the compile set becomes {segment widths} x {cache
    buckets} — O(log² max_len) — instead of one executable per exact prompt
    length, which is what makes ``precompile()`` finite for these families.
    The last segment ends exactly at ``n_real``, so its final position's
    logits are the first-token logits.
    """
    if cfg.input_kind != "tokens":
        raise ValueError(f"{cfg.name}: continuous batching serves token archs")
    from repro.models import layers as L
    from repro.models import lm

    def seg(params, cache, tokens, pos):
        hidden, new_cache, aux = lm.forward(
            cfg, params, {"tokens": tokens},
            cache=cache, cache_pos=pos, collector=collector,
        )
        logits = L.logits_fn(params, cfg, hidden[:, -1:])[0, 0]
        return new_cache, logits, aux.get("captures", {})

    return seg


def make_slot_decode_step(cfg: ModelConfig, collector: Collector = NULL_COLLECTOR) -> Callable:
    """Returns ``step(params, dense_cache, tokens [S], pos [S]) ->
    (dense_cache, logits [S, V], captures)`` with per-slot positions.

    ``dense_cache`` is the gathered paged view (see ``paged_cache.gather``);
    captures come out of ``lm.forward``'s aux so MegaScope probes yield
    per-slot records (stacked over the slot axis by vmap).
    """
    if cfg.input_kind != "tokens":
        raise ValueError(f"{cfg.name}: continuous batching serves token archs")
    from repro.models import layers as L
    from repro.models import lm

    def one(params, cache_s, tok, pos):
        cache_b = jax.tree.map(lambda a: a[:, None], cache_s)  # batch=1 back
        hidden, new_cache, aux = lm.forward(
            cfg, params, {"tokens": tok[None, None]},
            cache=cache_b, cache_pos=pos, collector=collector,
        )
        logits = L.logits_fn(params, cfg, hidden)[0, 0]
        new_cache = jax.tree.map(lambda a: a[:, 0], new_cache)
        return new_cache, logits, aux.get("captures", {})

    def step(params, cache, tokens, pos):
        return jax.vmap(one, in_axes=(None, 1, 0, 0), out_axes=(1, 0, 0))(
            params, cache, tokens, pos
        )

    return step


def make_slot_prefill(cfg: ModelConfig, collector: Collector = NULL_COLLECTOR) -> Callable:
    """Returns ``prefill(params, tokens [1, P], n_real, cache_len) ->
    (filled_cache, last_logits [V], captures)``.

    ``tokens`` may be right-padded to ``P >= n_real`` for attention-only
    families (the causal mask keeps real positions blind to pad garbage, and
    pad K/V land beyond ``kv_len`` where decode masks them); the logits are
    taken at ``n_real - 1`` regardless.  Recurrent-state families integrate
    every position, so their callers must pass exact-length prompts
    (``P == n_real``).  The cache is rounded up to a block multiple by the
    caller via ``cache_len``.
    """
    if cfg.input_kind != "tokens":
        raise ValueError(f"{cfg.name}: continuous batching serves token archs")
    from repro.models import layers as L
    from repro.models import lm

    def prefill(params, tokens, n_real, cache_len: int):
        cache = lm.init_cache(cfg, 1, cache_len)
        hidden, new_cache, aux = lm.forward(
            cfg, params, {"tokens": tokens},
            cache=cache, cache_pos=jnp.int32(0), collector=collector,
        )
        last = jax.lax.dynamic_slice_in_dim(hidden, n_real - 1, 1, axis=1)
        logits = L.logits_fn(params, cfg, last)[0, 0]
        return new_cache, logits, aux.get("captures", {})

    return prefill
