"""DeepSeek-V2 serving: the latent (MLA) paged cache and decode kernel, the
dropless chip-share expert layer, YaRN rope, against the plain float32
reference ``models.deepseek_v2_ref`` (seeded random weights, float32, small
sizes, on the CPU)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoEConfig, RopeScaling, get_config
from repro.models import deepseek_v2_ref as ref
from repro.models import layers as L
from repro.models import lm
from repro.serve import MegaServe, PagedKVCache, PoolSpec, ServeConfig
from repro.serve.engine import make_flash_prefill_step, make_paged_decode_step

BS = 8


def _perturb_scales(params, key):
    """Norm scales drawn around 1, so a path that drops one shows."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = iter(jax.random.split(key, len(leaves)))
    out = []
    for path, x in leaves:
        name = str(path[-1])
        k = next(keys)
        if "scale" in name or "kv_norm" in name:
            x = x + 0.1 * jax.random.normal(k, x.shape, x.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(tree, out)


def _cfg(**moe):
    base = get_config("deepseek-v2-lite-16b", smoke=True)
    return base.replace(
        compute_dtype="float32", attn_kv_chunk=4096,
        moe=MoEConfig(**{**base.moe.__dict__, **moe}))


@pytest.fixture(scope="module")
def ds():
    cfg = _cfg(num_experts=8, top_k=3)
    params = _perturb_scales(lm.init(cfg, jax.random.PRNGKey(0)),
                             jax.random.PRNGKey(1))
    return cfg, params


def _serve_logits(cfg, params, seq, n_prompt, impl, slot=1, slots=3):
    """Prefill ``seq[:n_prompt]`` through the flash (whole-prompt) step into
    the latent pool, then decode ``seq[n_prompt:]`` one token a step through
    the paged decode step (teacher-forced).  Returns the logits of the
    prefill's last position and of each decode step, [len(seq) - n_prompt
    + 1, V]."""
    n_steps = len(seq) - n_prompt
    width = -(-len(seq) // BS)
    kv = PagedKVCache(cfg, PoolSpec(num_slots=slots, num_blocks=1 + width,
                                    block_size=BS, max_blocks=width),
                      promote_store=True)
    table = np.zeros((slots, width), np.int32)
    table[slot] = np.arange(1, width + 1)[::-1]      # scattered, not 1..n
    prefill = jax.jit(make_flash_prefill_step(
        cfg, block_size=BS, paged_flags=kv.paged, impl=impl))
    decode = jax.jit(make_paged_decode_step(
        cfg, block_size=BS, paged_flags=kv.paged, impl=impl))
    p_blk = -(-n_prompt // BS)
    toks = np.zeros((1, p_blk * BS), np.int32)
    toks[0, :n_prompt] = seq[:n_prompt]
    pool, lg, _ = prefill(params, kv.pool, jnp.asarray(table[slot:slot + 1,
                                                              :p_blk]),
                          jnp.asarray(toks), jnp.int32(n_prompt))
    out = [np.asarray(lg)]
    for j in range(n_steps - 1):
        tok = np.zeros(slots, np.int32)
        pos = np.zeros(slots, np.int32)
        tok[slot], pos[slot] = seq[n_prompt + j], n_prompt + j
        pool, lg, caps = decode(params, pool, jnp.asarray(table),
                                jnp.asarray(tok), jnp.asarray(pos))
        out.append(np.asarray(lg[slot]))
    return np.stack(out), caps["moe_held_picks"]


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_prefill_then_latent_decode_matches_reference(ds, impl):
    """Prefill then decode through the latent paged cache agree with the
    reference's full forward, on logits.  The tolerance is the pool's:
    cached rows round through bfloat16 (relative 2^-9), which moves the
    logits by 0.47% of their spread here; 1% of the spread leaves twice
    that, and fails on a lower precision or a wrong term (computing in
    bfloat16 throughout reads 11%, a dropped YaRN softmax scale 43%)."""
    cfg, params = ds
    rng = np.random.default_rng(0)
    seq = rng.integers(2, cfg.vocab_size, size=29).tolist()
    n_prompt = 13
    got, _ = _serve_logits(cfg, params, seq, n_prompt, impl)
    want = np.asarray(ref.logits(params, cfg, seq))[n_prompt - 1: len(seq) - 1]
    spread = want.max() - want.min()
    assert np.abs(got[:, : cfg.vocab_size] - want).max() < 1e-2 * spread


def test_reference_catches_a_missing_yarn_scale(ds):
    """The comparison above is tight enough to see the YaRN softmax scale:
    without it the program's logits leave the tolerance."""
    cfg, params = ds
    seq = np.random.default_rng(1).integers(2, cfg.vocab_size, 20).tolist()
    want = np.asarray(ref.logits(params, cfg, seq))
    plain = cfg.replace(rope_scaling=RopeScaling(
        factor=cfg.rope_scaling.factor, mscale=0.707, mscale_all_dim=0.0,
        original_max_position_embeddings=(
            cfg.rope_scaling.original_max_position_embeddings)))
    h, _, _ = lm.forward(plain, params, {"tokens": jnp.asarray([seq])})
    got = np.asarray(L.logits_fn(params, plain, h))[0, :, : cfg.vocab_size]
    assert np.abs(got - want).max() > 1e-2 * (want.max() - want.min())


def test_full_forward_matches_reference(ds):
    """The training-shape forward (expanded MLA, no cache) against the
    reference: float32 on both sides, only summation order differs."""
    cfg, params = ds
    seq = np.random.default_rng(2).integers(2, cfg.vocab_size, 24).tolist()
    h, _, aux = lm.forward(cfg, params, {"tokens": jnp.asarray([seq])})
    got = np.asarray(L.logits_fn(params, cfg, h))[0, :, : cfg.vocab_size]
    want = np.asarray(ref.logits(params, cfg, seq))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    picks = np.asarray(aux["moe_held_picks"])
    assert picks.shape == (cfg.num_layers - 1, cfg.moe.num_experts)
    assert (picks.sum(1) == len(seq) * cfg.moe.top_k).all()


def _moe_layer(cfg, key):
    b = L.ParamBuilder(key)
    L.moe_init(b, cfg)
    p = b.params
    return p


def test_chip_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4: each share routes over all 16 and
    computes its own experts' part; the parts, with the shared experts
    counted once, add up to the reference's whole layer."""
    cfg = _cfg(num_experts=16, top_k=4, num_shared_experts=1)
    full = _moe_layer(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 6, cfg.d_model))
    shared = L._shared_experts(full["shared"], x)
    total = shared
    for k in range(4):
        part = dict(full)
        for w in ("w_gate", "w_up", "w_down"):
            part[w] = full[w][4 * k: 4 * k + 4]
        share_cfg = cfg.replace(moe=MoEConfig(
            **{**cfg.moe.__dict__, "experts_held": 4, "expert_offset": 4 * k}))
        y, aux = L.moe_apply(part, share_cfg, x)
        assert aux["moe_held_picks"].shape == (4,)
        total = total + (y - shared)
    want = jax.vmap(lambda h: ref.moe(full, cfg, h))(x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_expert_layer_is_dropless():
    """A token's result does not depend on what else shares the batch: the
    same sequence alone and beside 5 others whose picks pile onto one
    expert (what a capacity limit would drop)."""
    cfg = _cfg(num_experts=8, top_k=2)
    p = _moe_layer(cfg, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 7, cfg.d_model))
    # batch-mates aligned with the router's first column all pick expert 0
    mates = jnp.broadcast_to(10.0 * p["router"][:, 0], (5, 7, cfg.d_model))
    alone, _ = L.moe_apply(p, cfg, x)
    crowd, aux = L.moe_apply(p, cfg, jnp.concatenate([x, mates]))
    assert int(aux["moe_held_picks"][0]) >= 35
    np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(crowd[0]),
                               rtol=1e-6, atol=1e-6)


def test_served_logits_do_not_depend_on_batch_mates(ds):
    """End to end: a slot's decode logits alone equal its logits beside two
    busy slots (dropless experts, per-slot latent attention)."""
    cfg, params = ds
    rng = np.random.default_rng(7)
    seq = rng.integers(2, cfg.vocab_size, 20).tolist()
    alone, _ = _serve_logits(cfg, params, seq, 9, "xla", slot=0, slots=1)
    width = -(-len(seq) // BS)
    kv = PagedKVCache(cfg, PoolSpec(num_slots=3, num_blocks=1 + 3 * width,
                                    block_size=BS, max_blocks=width),
                      promote_store=True)
    prefill = jax.jit(make_flash_prefill_step(
        cfg, block_size=BS, paged_flags=kv.paged, impl="xla"))
    decode = jax.jit(make_paged_decode_step(
        cfg, block_size=BS, paged_flags=kv.paged, impl="xla"))
    table = np.arange(1, 1 + 3 * width, dtype=np.int32).reshape(3, width)
    seqs = [seq] + [rng.integers(2, cfg.vocab_size, 20).tolist()
                    for _ in range(2)]
    pool = kv.pool
    p_blk = -(-9 // BS)
    for s, q in enumerate(seqs):
        toks = np.zeros((1, p_blk * BS), np.int32)
        toks[0, :9] = q[:9]
        pool, lg, _ = prefill(params, pool, jnp.asarray(table[s:s + 1, :p_blk]),
                              jnp.asarray(toks), jnp.int32(9))
        if s == 0:
            out = [np.asarray(lg)]
    for j in range(len(seq) - 10):
        tok = np.asarray([q[9 + j] for q in seqs], np.int32)
        pos = np.full(3, 9 + j, np.int32)
        pool, lg, _ = decode(params, pool, jnp.asarray(table),
                             jnp.asarray(tok), jnp.asarray(pos))
        out.append(np.asarray(lg[0]))
    np.testing.assert_allclose(np.stack(out), alone, rtol=1e-5, atol=1e-5)


def test_gates_are_not_renormalised_where_the_config_says_so():
    """norm_topk_prob false: the top-k softmax gates stay as they are
    (DeepSeek-V2); true renormalises them (phi3.5-MoE)."""
    cfg = _cfg(num_experts=8, top_k=3, norm_topk_prob=False)
    p = _moe_layer(cfg, jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 5, cfg.d_model))
    _, _, gate, _ = L._route(p, cfg, x[0])
    assert float(gate.sum(-1).max()) < 0.999
    y, _ = L.moe_apply(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y[0]),
                               np.asarray(ref.moe(p, cfg, x[0])),
                               rtol=1e-5, atol=1e-5)
    norm = cfg.replace(moe=MoEConfig(**{**cfg.moe.__dict__,
                                        "norm_topk_prob": True}))
    _, _, gate_n, _ = L._route(p, norm, x[0])
    np.testing.assert_allclose(np.asarray(gate_n.sum(-1)), 1.0, rtol=1e-6)
    y_n, _ = L.moe_apply(p, norm, x)
    assert np.abs(np.asarray(y_n - y)).max() > 1e-3


def test_yarn_matches_the_published_formulas():
    """DeepSeek-V2-Lite's YaRN: rope dim 64, base 10000, factor 40 over 4096
    positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707.
    Frequencies as ``DeepseekV2YarnRotaryEmbedding`` computes them (written
    out here in float64), and the softmax scale ``192^-0.5 * mscale^2``."""
    cfg = get_config("deepseek-v2-lite-16b")
    rs = cfg.rope_scaling
    assert (rs.factor, rs.original_max_position_embeddings) == (40.0, 4096)
    dim, base = 64, 10000.0

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23) == L.yarn_ramp_range(dim, base, rs)
    extra = base ** -(np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(np.asarray(L.rope_freqs(dim, base, rs)), want,
                               rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert L.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert L.mla_softmax_scale(cfg) / 192 ** -0.5 == pytest.approx(1.59, abs=0.01)
    # cos / sin keep unit scale: mscale and mscale_all_dim are equal
    x = jnp.ones((1, 3, 1, dim))
    r = L.apply_rope(x, jnp.zeros((3,), jnp.int32), base, rs)
    np.testing.assert_allclose(np.asarray(r), 1.0)


def _latent_case(seed, S, bs, H, r, dr, kv_lens, n_layers):
    from repro.serve.paged_cache import blocks_for

    rng = np.random.default_rng(seed)
    W = -(-(r + dr) // 128) * 128
    live = [blocks_for(int(n), bs) for n in kv_lens]
    M = max(live) + 1                         # one dead entry past the end
    nb = 1 + sum(live)
    q = np.zeros((S, H, W), np.float32)
    q[..., : r + dr] = rng.standard_normal((S, H, r + dr))
    pool = np.zeros((n_layers, nb, bs, W), np.float32)
    pool[..., : r + dr] = rng.standard_normal((n_layers, nb, bs, r + dr))
    tables = np.zeros((S, M), np.int32)
    perm = rng.permutation(np.arange(1, nb))
    i = 0
    for s in range(S):
        tables[s, : live[s]] = perm[i: i + live[s]]
        i += live[s]
    return (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
            jnp.asarray(kv_lens, jnp.int32))


@pytest.mark.parametrize("bs,pages", [(8, 1), (8, 3), (16, 2), (16, None)])
def test_latent_kernel_interpret_matches_ref(bs, pages):
    """The latent decode kernel (interpret mode) against its jnp ref: block
    sizes, ragged kv_len (1, a block, several chunks), a layer-stacked pool,
    and the multi-block walk (``pages`` table entries a loop iteration; a
    dead entry past each slot's end)."""
    from repro.kernels.paged_attention import (
        paged_latent_attention_pallas,
        paged_latent_attention_ref,
    )

    q, pool, tables, kv_len = _latent_case(
        bs + (pages or 0), S=4, bs=bs, H=4, r=32, dr=8,
        kv_lens=[1, bs, 5 * bs + 3, 2 * bs - 1], n_layers=3)
    lay = jnp.int32(2)
    want = paged_latent_attention_ref(q, pool, tables, kv_len, r=32,
                                      scale=0.3, layer=lay)
    got = paged_latent_attention_pallas(q, pool, tables, kv_len, r=32,
                                        scale=0.3, pages=pages,
                                        interpret=True, layer=lay)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stacked", [False, True])
def test_expert_kernel_interpret_matches_ref(stacked):
    """The grouped expert SwiGLU kernel (interpret mode) against its ref:
    an empty group, groups sharing a row tile, rows of absent experts past
    the held ones (left to the caller), tiles over the expert width; one
    layer's weights, or a layer of weights stacked over layers and read in
    place."""
    from repro.kernels.moe import expert_ffn_pallas, expert_ffn_ref

    rng = np.random.default_rng(10)
    m, D, F, G, n = 45, 32, 24, 4, 3
    x = jnp.asarray(rng.standard_normal((m, D)), jnp.float32)
    w = [jnp.asarray(rng.standard_normal(s), jnp.float32)
         for s in ((n, G, D, F), (n, G, D, F), (n, G, F, D))]
    sizes = jnp.asarray([5, 0, 19, 9], jnp.int32)
    want = expert_ffn_ref(x, *[a[1] for a in w], sizes)
    if stacked:
        got = expert_ffn_pallas(x, *w, sizes, tm=8, tf=8, interpret=True,
                                layer=jnp.int32(1))
    else:
        got = expert_ffn_pallas(x, *[a[1] for a in w], sizes, tm=8, tf=8,
                                interpret=True)
    n = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               rtol=1e-5, atol=1e-3)
    assert not np.asarray(want)[n:].any()


def test_server_serves_mla_on_the_paged_path_and_counts_expert_load(ds):
    """``MegaServe`` resolves MLA to the paged (latent) decode and the
    whole-prompt prefill, serves the same tokens as the gathered oracle,
    and records the decode calls' expert load in the registry."""
    from repro.obs import MetricsRegistry

    cfg, params = ds
    rng = np.random.default_rng(11)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist()
               for n in (5, 17, 30)]
    outs = {}
    for path, ppath in (("paged", "flash"), ("gathered", "dense")):
        reg = MetricsRegistry()
        srv = MegaServe(cfg, params, ServeConfig(
            num_slots=3, block_size=BS, num_blocks=40, max_blocks_per_slot=8,
            decode_path=path, prefill_path=ppath), registry=reg)
        assert (srv.decode_path, srv.prefill_path) == (path, ppath)
        for p in prompts:
            srv.submit(p, 6, arrival=0.0)
        outs[path] = srv.drain()
        snap = reg.snapshot()
        if path == "paged":
            held = snap["serve.moe.held_picks"]
            top = snap["serve.moe.max_expert_picks"]
            assert held["count"] == top["count"] > 0
            assert top["sum"] >= held["sum"] / cfg.moe.num_experts
        else:
            assert "serve.moe.held_picks" not in snap
    assert outs["paged"] == outs["gathered"]


@pytest.mark.parametrize("knob", ["spec_decode", "chunked_prefill",
                                  "prefill_only"])
def test_mla_refusals_name_what_they_refuse(ds, knob):
    cfg, params = ds
    kw = {"num_slots": 2, "block_size": BS, "num_blocks": 20,
          "max_blocks_per_slot": 4}
    extra = {}
    if knob == "prefill_only":
        extra["prefill_only"] = True
    else:
        kw[knob] = True
    with pytest.raises(ValueError, match="MLA"):
        MegaServe(cfg, params, ServeConfig(**kw), **extra)
