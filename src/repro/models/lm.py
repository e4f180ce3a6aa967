"""Decoder-only LM assembly for all families (dense / moe / rwkv6 / griffin).

Layers are stacked into *segments* (runs of identical repeating structure) and
executed with ``lax.scan`` + per-layer remat, keeping HLO size O(1) in depth:

  dense/moe : [ (first_k_dense dense blocks) ] + [ (moe|dense block) x N ]
  rwkv6     : [ rwkv block x N ]
  griffin   : [ (rec, rec, attn) x N ] + [ remainder blocks ]
"""

from __future__ import annotations

import math
from dataclasses import replace
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import griffin as gf
from repro.models import rwkv as rk
from repro.models import layers as L
from repro.models.hooks import Collector, LayerScoped, NULL_COLLECTOR
from repro.parallel.sharding import shard_act

# ---------------------------------------------------------------------------
# segment layout
# ---------------------------------------------------------------------------


def maybe_scan(body, carry, xs, n: int, unroll: bool):
    """lax.scan, or an unrolled python loop (cost-probe configs: while-loop
    bodies are counted once by HLO cost analysis, so probes unroll)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    ys_all = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys_all.append(y)
    ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys_all)
    return carry, ys


def segment_layout(cfg: ModelConfig) -> list[tuple[tuple[str, ...], int]]:
    """Returns [(block_kinds_per_group, n_groups), ...] covering all layers."""
    if cfg.family == "dense":
        return [(("dense",), cfg.num_layers)]
    if cfg.family == "moe":
        segs = []
        fk = cfg.moe.first_k_dense
        if fk:
            segs.append((("dense",), fk))
        segs.append((("moe",), cfg.num_layers - fk))
        return segs
    if cfg.family == "rwkv6":
        return [(("rwkv",), cfg.num_layers)]
    if cfg.family == "griffin":
        pat = cfg.griffin.pattern
        n_full, rem = divmod(cfg.num_layers, len(pat))
        segs = []
        if n_full:
            segs.append((pat, n_full))
        if rem:
            segs.append((pat[:rem], 1))
        return segs
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# block init / apply dispatch
# ---------------------------------------------------------------------------


def _block_init(b: L.ParamBuilder, cfg: ModelConfig, kind: str) -> None:
    if kind == "rwkv":
        rk.rwkv_block_init(b, cfg)
        return
    if kind in ("rec", "attn"):
        gf.griffin_block_init(b, cfg, kind)
        return
    L.norm_init(b, "ln1", cfg.d_model, cfg.norm_kind)
    L.norm_init(b, "ln2", cfg.d_model, cfg.norm_kind)
    if cfg.use_mla:
        L.mla_init(b.sub("attn"), cfg)
    else:
        L.gqa_init(b.sub("attn"), cfg)
    if kind == "moe":
        L.moe_init(b.sub("mlp"), cfg)
    else:
        L.mlp_init(b.sub("mlp"), cfg)


def _resid(cfg: ModelConfig, x: jax.Array, delta: jax.Array) -> jax.Array:
    if cfg.scale_depth:
        return x + delta * (cfg.scale_depth / math.sqrt(cfg.num_layers))
    return x + delta


def _block_apply(
    p: dict,
    cfg: ModelConfig,
    kind: str,
    x: jax.Array,
    *,
    positions: jax.Array,
    cache: dict | None,
    cache_pos: jax.Array | None,
    mrope_position_ids: jax.Array | None,
    paged: Any | None,
    collector: Collector,
    layer: jax.Array | None = None,
) -> tuple[jax.Array, dict | None, dict]:
    # anchor the block input: the constraint's transpose pins the residual
    # *gradient* sharding in backward (GSPMD can otherwise fully replicate it
    # on multi-axis meshes — "involuntary full rematerialization")
    x = shard_act(x, ("batch", "seq_act", "embed_act"))
    if kind == "rwkv":
        x, st = rk.rwkv_block_apply(p, cfg, x, state=cache, collector=collector)
        return x, st, {}
    if kind in ("rec", "attn"):
        x, st = gf.griffin_block_apply(
            p, cfg, kind, x,
            positions=positions, state=cache, cache_pos=cache_pos,
            paged=paged, collector=collector,
        )
        return x, st, {}
    aux: dict = {}
    h = L.norm_apply(p["ln1"], x, cfg.norm_kind, cfg.norm_eps)
    if cfg.use_mla:
        a, new_cache = L.mla_apply(
            p["attn"], cfg, h, positions=positions, cache=cache,
            cache_pos=cache_pos, paged=paged, collector=collector,
        )
    else:
        a, new_cache = L.gqa_apply(
            p["attn"], cfg, h, positions=positions, cache=cache,
            cache_pos=cache_pos, mrope_position_ids=mrope_position_ids,
            paged=paged, collector=collector,
        )
    x = _resid(cfg, x, collector.tag("att_resid", a))
    h = L.norm_apply(p["ln2"], x, cfg.norm_kind, cfg.norm_eps)
    if kind == "moe":
        f, aux = L.moe_apply(
            p["mlp"], cfg, h, n_seq_groups=cfg.moe.seq_groups,
            collector=collector, layer=layer,
        )
    else:
        f = L.mlp_apply(p["mlp"], cfg, h, collector)
    x = _resid(cfg, x, collector.tag("ffn_resid", f))
    x = shard_act(x, ("batch", "seq_act", "embed_act"))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# stacked-segment parameter construction
# ---------------------------------------------------------------------------


def _group_init(b: L.ParamBuilder, cfg: ModelConfig, kinds: tuple[str, ...]) -> None:
    for j, kind in enumerate(kinds):
        _block_init(b.sub(f"b{j}"), cfg, kind)


def _prepend_layers_axis(axes_tree: Any) -> Any:
    is_axes = lambda t: isinstance(t, tuple) and all(
        isinstance(a, (str, type(None))) for a in t
    )
    return jax.tree.map(
        lambda t: ("layers", *t), axes_tree, is_leaf=is_axes
    )


def init(cfg: ModelConfig, key: jax.Array) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    b = L.ParamBuilder(key, dtype)
    L.embed_init(b, cfg)
    L.norm_init(b, "final_norm", cfg.d_model, cfg.norm_kind)
    for i, (kinds, n) in enumerate(segment_layout(cfg)):
        seg_key = b.split()

        def one(k, kinds=kinds):
            gb = L.ParamBuilder(k, dtype)
            _group_init(gb, cfg, kinds)
            return gb.params

        b.params[f"seg{i}"] = jax.vmap(one)(jax.random.split(seg_key, n))
    return b.params


def param_axes(cfg: ModelConfig) -> dict:
    captured: dict = {}

    def run(key):
        b = L.ParamBuilder(key, jnp.dtype(cfg.param_dtype))
        L.embed_init(b, cfg)
        L.norm_init(b, "final_norm", cfg.d_model, cfg.norm_kind)
        captured.update(b.axes)
        return b.params

    jax.eval_shape(run, jax.random.PRNGKey(0))
    for i, (kinds, n) in enumerate(segment_layout(cfg)):
        seg_cap: dict = {}

        def run_g(key, kinds=kinds, seg_cap=seg_cap):
            gb = L.ParamBuilder(key, jnp.dtype(cfg.param_dtype))
            _group_init(gb, cfg, kinds)
            seg_cap.update(gb.axes)
            return gb.params

        jax.eval_shape(run_g, jax.random.PRNGKey(0))
        captured[f"seg{i}"] = _prepend_layers_axis(seg_cap)
    return captured


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _split_cache(tree: Any, flags: Any) -> tuple[Any, Any]:
    """Partition a nested-dict cache by a mirrored bool tree into
    (flagged, unflagged) trees of identical structure with ``None`` at the
    dropped leaf positions (``None`` leaves are empty pytrees, so scan/vmap
    simply skip them)."""
    if isinstance(tree, dict):
        a, b = {}, {}
        for k, v in tree.items():
            a[k], b[k] = _split_cache(v, flags[k])
        return a, b
    return (tree, None) if flags else (None, tree)


def _merge_cache(a: Any, b: Any) -> Any:
    """Inverse of ``_split_cache``: overlay two structurally-identical trees
    with complementary ``None`` leaves."""
    if isinstance(a, dict):
        return {k: _merge_cache(a[k], b[k]) for k in a}
    return a if b is None else b


def _split_experts(seg_p: dict, kinds: tuple[str, ...]) -> tuple[dict, dict]:
    """Take the routed experts' weights of a segment's MoE blocks out of the
    per-layer scan inputs: ``(rest, stacks)``, ``stacks[f"b{j}"]`` the
    ``[n_layers, experts, ...]`` arrays.  The scan body hands them to the
    expert layer whole, with the layer index: a per-layer slice of them would
    be materialized for the grouped kernel on every step of the loop."""
    rest, stacks = dict(seg_p), {}
    for j, kind in enumerate(kinds):
        if kind != "moe":
            continue
        blk = dict(rest[f"b{j}"])
        mlp = dict(blk["mlp"])
        stacks[f"b{j}"] = {k: mlp.pop(k) for k in L.EXPERT_WEIGHTS}
        blk["mlp"] = mlp
        rest[f"b{j}"] = blk
    return rest, stacks


def _embed_inputs(cfg: ModelConfig, params: dict, batch: dict, dtype) -> jax.Array:
    if cfg.input_kind == "tokens":
        return L.embed_apply(params, cfg, batch["tokens"], dtype)
    x = batch["embeds"].astype(dtype)
    if cfg.scale_emb != 1.0:
        x = x * cfg.scale_emb
    return shard_act(x, ("batch", "seq_act", "embed_act"))


def forward(
    cfg: ModelConfig,
    params: dict,
    batch: dict,
    *,
    cache: dict | None = None,
    cache_pos: jax.Array | None = None,
    paged: Any | None = None,
    paged_flags: Any | None = None,
    collector: Collector = NULL_COLLECTOR,
) -> tuple[jax.Array, dict | None, dict]:
    """Returns (hidden [B,S,D], new_cache, aux).

    ``aux["moe_held_picks"]`` (MoE families on the dropless expert layer)
    counts the routing picks that landed on each held expert, per MoE layer:
    ``[n_moe_layers, experts_held]`` int32.

    When ``paged`` (a ``kernels.paged_attention.ops.PagedInfo``) is set, the
    attention leaves of ``cache`` are layer-stacked physical pool arrays
    shared across the batch, ``cache_pos`` is a per-row ``[B]`` vector of
    slot positions, and attention streams K/V blocks via the paged kernel
    instead of a dense cache (see ``serve.engine.make_paged_decode_step``).
    ``paged_flags`` (a bool tree mirroring ``cache``, e.g.
    ``PagedKVCache.paged``) marks which leaves are pools: those ride the
    layer scan's *carry* and are updated in place by layer-indexed scatters —
    scanning them through xs/ys would re-stack the entire pool every decode
    step, turning an O(kv_len) step back into an O(pool) one.  Slot-state
    leaves (rwkv/griffin recurrent state) stay in xs/ys as usual.
    """
    dtype = jnp.dtype(cfg.compute_dtype)
    x = _embed_inputs(cfg, params, batch, dtype)
    B, S, _ = x.shape
    if cache_pos is None:
        positions = jnp.arange(S)
    elif jnp.ndim(cache_pos) == 1:  # per-slot positions (paged decode)
        positions = cache_pos[:, None] + jnp.arange(S)[None, :]
    else:
        positions = cache_pos + jnp.arange(S)
    mrope_ids = batch.get("mrope_position_ids")
    x = collector.tag("embeddings", x)

    aux_losses = jnp.zeros((), jnp.float32)
    aux_metrics: dict[str, jax.Array] = {}
    captures_by_seg: dict[str, dict] = {}
    held_picks: list[jax.Array] = []   # [n, held] picks per held expert
    new_cache: dict = {}
    layer_offset = 0
    for i, (kinds, n) in enumerate(segment_layout(cfg)):
        seg_p, experts = _split_experts(params[f"seg{i}"], kinds)
        seg_cache = cache.get(f"seg{i}") if cache is not None else None
        if paged is not None and seg_cache is not None:
            seg_flags = paged_flags[f"seg{i}"]
            seg_pool, seg_state = _split_cache(seg_cache, seg_flags)
        else:
            seg_flags, seg_pool, seg_state = None, None, seg_cache

        def body(carry, xs, kinds=kinds, offset=layer_offset, flags=seg_flags,
                 experts=experts):
            xc, aux_c, pool_c = carry
            layer_p, layer_cache, g = xs
            new_layer_cache = {} if layer_cache is not None else None
            captured = {}
            for j, kind in enumerate(kinds):
                # the inert collector stays itself: blocks test for it to
                # take fused paths (flash prefill) that tag nothing
                col = (
                    collector if collector is NULL_COLLECTOR
                    else LayerScoped(collector, offset + g * len(kinds) + j)
                )
                blk_cache = None if layer_cache is None else layer_cache[f"b{j}"]
                blk_paged = None
                if pool_c is not None:
                    # overlay this block's pool leaves (full stacks from the
                    # carry, addressed at layer g) onto its slot-state slice
                    blk_cache = _merge_cache(pool_c[f"b{j}"], blk_cache)
                    blk_paged = replace(paged, layer=g)
                blk_p = layer_p[f"b{j}"]
                if f"b{j}" in experts:
                    blk_p = {**blk_p, "mlp": {**blk_p["mlp"], **experts[f"b{j}"]}}
                xc, c_new, aux = _block_apply(
                    blk_p, cfg, kind, xc,
                    positions=positions,
                    cache=blk_cache,
                    cache_pos=cache_pos,
                    mrope_position_ids=mrope_ids,
                    paged=blk_paged,
                    collector=col,
                    layer=g if f"b{j}" in experts else None,
                )
                if pool_c is not None and c_new is not None:
                    p_new, c_new = _split_cache(c_new, flags[f"b{j}"])
                    pool_c = {**pool_c, f"b{j}": p_new}
                if new_layer_cache is not None:
                    new_layer_cache[f"b{j}"] = c_new
                if aux:
                    aux_c = aux_c + aux.get("moe_aux_loss", 0.0)
                    captured["moe_drop_frac"] = aux.get("moe_drop_frac", 0.0)
                    if "moe_held_picks" in aux:
                        captured["moe_held_picks"] = aux["moe_held_picks"]
                probes = col.drain()
                if probes:
                    pre = f"b{j}/" if len(kinds) > 1 else ""
                    captured.update({pre + k: v for k, v in probes.items()})
            ys = (new_layer_cache, captured)
            return (xc, aux_c, pool_c), ys

        if cfg.remat != "none":
            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if cfg.remat == "dots"
                else jax.checkpoint_policies.nothing_saveable
            )
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)

        xs = (seg_p, seg_state, jnp.arange(n))
        (x, aux_losses, seg_pool), (seg_new_cache, cap) = maybe_scan(
            body, (x, aux_losses, seg_pool), xs, n, cfg.scan_unroll
        )
        if seg_cache is not None:
            new_cache[f"seg{i}"] = (
                _merge_cache(seg_pool, seg_new_cache)
                if seg_pool is not None else seg_new_cache
            )
        if cap:
            if "moe_drop_frac" in cap:
                aux_metrics[f"seg{i}_moe_drop_frac"] = cap["moe_drop_frac"].mean()
            if "moe_held_picks" in cap:
                held_picks.append(cap["moe_held_picks"])
            rest = {k: v for k, v in cap.items()
                    if k not in ("moe_drop_frac", "moe_held_picks")}
            if rest:
                captures_by_seg[f"seg{i}"] = rest
        layer_offset += n * len(kinds)

    x = L.norm_apply(params["final_norm"], x, cfg.norm_kind, cfg.norm_eps)
    x = collector.tag("final_hidden", x)
    aux = {"aux_loss": aux_losses, **aux_metrics}
    if held_picks:
        aux["moe_held_picks"] = jnp.concatenate(held_picks, axis=0)
    top = collector.drain()
    if top or captures_by_seg:
        aux["captures"] = dict(captures_by_seg)
        if top:
            aux["captures"]["top"] = top
    return x, (new_cache if cache is not None else None), aux


def loss_fn(
    cfg: ModelConfig,
    params: dict,
    batch: dict,
    collector: Collector = NULL_COLLECTOR,
) -> tuple[jax.Array, dict]:
    hidden, _, aux = forward(cfg, params, batch, collector=collector)
    aux.pop("moe_held_picks", None)   # a serving counter, not a loss metric
    total, count = L.chunked_xent(
        params, cfg, hidden, batch["targets"], batch.get("loss_mask")
    )
    ce = total / jnp.maximum(count, 1.0)
    loss = ce + aux["aux_loss"]
    metrics = {"loss": loss, "ce": ce, **aux}
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    cache: dict = {}
    for i, (kinds, n) in enumerate(segment_layout(cfg)):
        def one_group(kinds=kinds):
            out = {}
            for j, kind in enumerate(kinds):
                if kind == "rwkv":
                    out[f"b{j}"] = rk.rwkv_init_state(cfg, batch)
                elif kind in ("rec", "attn"):
                    out[f"b{j}"] = gf.griffin_init_state(cfg, kind, batch, cache_len)
                elif cfg.use_mla:
                    out[f"b{j}"] = {"latent": jnp.zeros(
                        (batch, cache_len, L.mla_cache_width(cfg)),
                        jnp.bfloat16)}
                else:
                    out[f"b{j}"] = {
                        "k": jnp.zeros(
                            (batch, cache_len, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16
                        ),
                        "v": jnp.zeros(
                            (batch, cache_len, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16
                        ),
                    }
            return out

        g = one_group()
        cache[f"seg{i}"] = jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf, (n, *leaf.shape)).copy()
            if hasattr(leaf, "shape")
            else leaf,
            g,
        )
    return cache


def prefill(
    cfg: ModelConfig,
    params: dict,
    batch: dict,
    cache: dict,
    collector: Collector = NULL_COLLECTOR,
) -> tuple[dict, jax.Array]:
    """Run the prompt through the model, filling the cache.  Returns
    (cache, last-position logits [B, V])."""
    hidden, new_cache, _ = forward(
        cfg, params, batch, cache=cache, cache_pos=jnp.int32(0), collector=collector
    )
    logits = L.logits_fn(params, cfg, hidden[:, -1:, :])[:, 0]
    return new_cache, logits


def decode_step(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B] or [B,1] token ids (or [B,1,D] embeds)
    pos: jax.Array,  # scalar int32: current position (number of cached tokens)
    collector: Collector = NULL_COLLECTOR,
) -> tuple[dict, jax.Array]:
    if cfg.input_kind == "tokens":
        tok = tokens.reshape(-1, 1)
        batch = {"tokens": tok}
    else:
        batch = {"embeds": tokens.reshape(tokens.shape[0], 1, -1)}
        if cfg.input_kind == "embeds_mrope":
            B = batch["embeds"].shape[0]
            batch["mrope_position_ids"] = jnp.broadcast_to(
                pos, (3, B, 1)
            ).astype(jnp.int32)
    hidden, new_cache, _ = forward(
        cfg, params, batch, cache=cache, cache_pos=pos, collector=collector
    )
    logits = L.logits_fn(params, cfg, hidden)[:, 0]
    return new_cache, logits
