"""MegaServe: block-allocator invariants, paged gather/scatter roundtrips,
paged-attention kernel parity (interpret mode vs ref vs gathered-dense
oracle), scheduler admission/eviction/preemption on scripted traces,
continuous-vs-static greedy equivalence on both decode paths, prefill
compile-cache bucketing, simkit policy evaluation, and trace emission."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.simkit.engine import Engine
from repro.core.simkit.workload import (
    RequestSpec,
    poisson_requests,
    serving_throughput,
    serving_workload,
)
from repro.core.tracing.chrome import to_chrome
from repro.models import get_model, lm
from repro.serve import (
    BlockAllocator,
    MegaServe,
    PagedKVCache,
    PoolSpec,
    Request,
    RequestStatus,
    Scheduler,
    ServeConfig,
    blocks_for,
)
from repro.serve.server import StaticRunner

# ------------------------------------------------------------ allocator ---


def test_allocator_alloc_free_invariants():
    a = BlockAllocator(num_blocks=8, reserved=1)
    assert a.num_free == 7
    got = a.alloc(3)
    assert len(set(got)) == 3 and 0 not in got
    assert a.num_free == 4 and a.num_held == 3
    a.free(got[:2])
    assert a.num_free == 6 and a.num_held == 1
    # LIFO reuse: the most recently freed block comes back first
    assert a.alloc(1)[0] == got[1]


def test_allocator_oom_and_double_free():
    from repro.serve import PoolExhausted

    a = BlockAllocator(num_blocks=4)
    got = a.alloc(3)
    assert a.try_alloc(1) is None
    with pytest.raises(PoolExhausted):
        a.alloc(1)
    a.free([got[0]])
    with pytest.raises(ValueError):
        a.free([got[0]])          # double free
    with pytest.raises(ValueError):
        a.free([0])               # reserved null block was never handed out


def test_blocks_for():
    assert blocks_for(1, 8) == 1
    assert blocks_for(8, 8) == 1
    assert blocks_for(9, 8) == 2


# ---------------------------------------------------- paged gather/scatter ---


@pytest.fixture(scope="module")
def qwen_serve():
    cfg = get_config("qwen2-0.5b", smoke=True).replace(
        compute_dtype="float32", attn_kv_chunk=4096
    )
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_paged_prefill_gather_roundtrip(qwen_serve):
    cfg, _ = qwen_serve
    spec = PoolSpec(num_slots=2, num_blocks=9, block_size=8, max_blocks=4)
    kv = PagedKVCache(cfg, spec)
    assert any(jax.tree.leaves(kv.paged)), "qwen must have paged k/v leaves"

    # fill a B=1 dense cache (2 blocks worth) with random values
    template = jax.eval_shape(lambda: lm.init_cache(cfg, 1, 16))
    key = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    filled = jax.tree.map(
        lambda s: jax.random.normal(next(key), s.shape).astype(s.dtype), template
    )
    phys = jnp.asarray([3, 5], jnp.int32)
    pool = kv.scatter_prefill(kv.pool, filled, jnp.int32(1), phys)

    tables = np.zeros((2, 4), np.int32)
    tables[1, :2] = [3, 5]
    dense = kv.gather(pool, jnp.asarray(tables))

    flat_d, _ = jax.tree_util.tree_flatten(dense)
    flat_f, _ = jax.tree_util.tree_flatten(filled)
    flat_p, _ = jax.tree_util.tree_flatten(kv.paged)
    for d, f, paged in zip(flat_d, flat_f, flat_p):
        if paged:  # slot 1, first 16 positions == the filled cache
            np.testing.assert_array_equal(np.asarray(d[:, 1, :16]),
                                          np.asarray(f[:, 0]))
        else:      # slot-state row
            np.testing.assert_array_equal(np.asarray(d[:, 1]),
                                          np.asarray(f[:, 0]))


@pytest.mark.parametrize("arch", [
    "qwen2-0.5b", "deepseek-v2-lite-16b", "recurrentgemma-9b", "rwkv6-3b"])
def test_pool_stores_flattened_feature_axes(arch):
    """Paged leaves are stored ``[n, num_blocks, bs, prod(feat)]`` (GQA k/v
    as ``K*dh``, the tile the paged kernels read; MLA's one-axis latents
    unchanged); slot-state leaves keep ``[n, num_slots, *feat]``."""
    import math

    cfg = get_config(arch, smoke=True)
    spec = PoolSpec(num_slots=2, num_blocks=9, block_size=8, max_blocks=4)
    kv = PagedKVCache(cfg, spec)
    for p, d, paged in zip(jax.tree.leaves(kv.pool), jax.tree.leaves(kv.dense),
                           jax.tree.leaves(kv.paged)):
        n = d.shape[0]
        if paged:
            assert p.shape == (n, 9, 8, math.prod(d.shape[3:]))
            assert p.ndim == 4
        else:
            assert p.shape == (n, 2, *d.shape[2:])


def test_scatter_decode_touches_only_written_block(qwen_serve):
    cfg, _ = qwen_serve
    spec = PoolSpec(num_slots=2, num_blocks=9, block_size=8, max_blocks=4)
    kv = PagedKVCache(cfg, spec)
    tables = np.zeros((2, 4), np.int32)
    tables[0, :2] = [2, 4]
    tables[1, :2] = [6, 7]
    tables = jnp.asarray(tables)
    pos = jnp.asarray([9, 3], jnp.int32)   # slot0 writes block 1, slot1 block 0

    dense = kv.gather(kv.pool, tables)
    dense = jax.tree.map(lambda a: a + 1.0 if a.ndim > 2 else a, dense)
    pool = kv.scatter_decode(kv.pool, dense, tables, pos)
    for leaf, paged in zip(jax.tree.leaves(pool), jax.tree.leaves(kv.paged)):
        if not paged:
            continue
        arr = np.asarray(leaf)
        assert np.all(arr[:, 4] != 0)      # slot0's touched block written
        assert np.all(arr[:, 6] != 0)      # slot1's touched block written
        assert np.all(arr[:, 2] == 0)      # slot0's untouched block intact
        assert np.all(arr[:, 7] == 0)      # slot1's untouched block intact


# ------------------------------------------------- paged-attention kernel ---


def _rand_paged(seed, S, bs, K, G, dh, kv_lens):
    """Random pool + block tables + queries for ``S`` slots with ragged
    ``kv_lens``; every slot gets distinct physical blocks, padding entries
    point at the null block 0 (which holds garbage, as in live serving).
    The single-layer pool is ``[nb, bs, K*dh]``, as ``PagedKVCache`` stores
    it."""
    rng = np.random.default_rng(seed)
    live = [blocks_for(int(l), bs) for l in kv_lens]
    M = max(live)
    nb = 1 + sum(live)
    H = K * G
    q = jnp.asarray(rng.standard_normal((S, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((nb, bs, K * dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, bs, K * dh)), jnp.float32)
    tables = np.zeros((S, M), np.int32)
    perm = rng.permutation(np.arange(1, nb))
    i = 0
    for s in range(S):
        tables[s, : live[s]] = perm[i : i + live[s]]
        i += live[s]
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(kv_lens, jnp.int32)


@pytest.mark.parametrize("pages", [None, 1, 2, 4])
@pytest.mark.parametrize("bs,G", [(8, 1), (8, 4), (16, 2)])
@pytest.mark.parametrize("window", [None, 7])
def test_paged_kernel_interpret_matches_ref(bs, G, window, pages):
    from repro.kernels.paged_attention import (
        paged_attention_pallas,
        paged_attention_ref,
    )

    q, kp, vp, tables, kv_len = _rand_paged(
        seed=bs * 10 + G, S=4, bs=bs, K=2, G=G, dh=16,
        kv_lens=[1, bs, 2 * bs + 3, 3 * bs - 1],
    )
    ref = paged_attention_ref(q, kp, vp, tables, kv_len, scale=0.25, window=window)
    ker = paged_attention_pallas(
        q, kp, vp, tables, kv_len, scale=0.25, window=window, pages=pages,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=2e-6)


# (pages, kv_lens, Q, window, extra table entries): the live walk's edges.
# P*bs is 16 at pages 2 (bs 8), 32 at pages 4.
_WALK_CASES = {
    # one row, and a chunk's last row, its whole, and one past it
    "chunk_edges": (2, [1, 15, 16, 17], 1, None, 0),
    # a table 5 entries wide at 4 a chunk (not a multiple), and wider
    # than the longest slot needs
    "width_not_multiple": (4, [5, 20, 40], 1, None, 0),
    "width_padded": (4, [5, 20, 40], 1, None, 3),
    # a table narrower than one chunk
    "width_under_chunk": (4, [3, 12], 1, None, 0),
    # idle slots between long ones: each slot's last chunk starts the
    # next slot's first copies
    "idle_between_long": (2, [40, 1, 1, 37, 1, 50], 1, None, 0),
    # windows that start inside a chunk, and one wider than the slot
    "window_in_chunk": (2, [45, 30, 3, 61], 1, 13, 0),
    # the verify step: 4 queries a slot, causal inside the query block
    "verify_q4": (2, [4, 17, 33, 6], 4, None, 0),
    "verify_q4_window": (2, [4, 17, 33, 46], 4, 11, 0),
}


@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_paged_kernel_live_walk_matches_ref(case):
    """The kernel walks each slot's live blocks only, ``pages`` table
    entries a chunk, with copies double-buffered across chunks and slots:
    at every edge of that walk it must agree with the XLA ref."""
    from repro.kernels.paged_attention import (
        paged_attention_pallas,
        paged_attention_ref,
    )

    pages, kv_lens, Q, window, extra = _WALK_CASES[case]
    S = len(kv_lens)
    q, kp, vp, tables, kv_len = _rand_paged(
        seed=len(case), S=S, bs=8, K=2, G=2, dh=16, kv_lens=kv_lens)
    tables = jnp.pad(tables, ((0, 0), (0, extra)))
    if Q > 1:
        rng = np.random.default_rng(len(case))
        q = jnp.asarray(rng.standard_normal((S, Q, *q.shape[1:])), jnp.float32)
    ref = paged_attention_ref(q, kp, vp, tables, kv_len, scale=0.25,
                              window=window)
    ker = paged_attention_pallas(q, kp, vp, tables, kv_len, scale=0.25,
                                 window=window, pages=pages, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=2e-6)


@pytest.mark.parametrize("window", [None, 13])
@pytest.mark.parametrize("q_len", [1, 4])
def test_walk_chunks_counts_live_chunks(q_len, window):
    """``walk_chunks`` (the host's count behind ``paged_decode_chunks``)
    gives, per slot, the chunks between the first and the last table entry
    holding a key that some query attends."""
    from repro.kernels.paged_attention.kernel import walk_chunks

    bs, P, M = 8, 2, 9
    kv = np.arange(1, M * bs + 1)
    got = walk_chunks(kv, block_size=bs, n_entries=M, q_len=q_len,
                      window=window, pages=P)
    for n, g in zip(kv, got):
        keys = np.arange(n)
        lo = n - (q_len - 1) - window if window else 0
        live = np.unique(keys[keys >= max(lo, 0)] // bs)
        assert g == (live[-1] - live[0]) // P + 1, (n, g)


def test_paged_ref_matches_gathered_dense_oracle():
    """The paged ref must agree with dense decode attention over the
    materialized per-slot view — i.e. with what the gathered oracle path
    computes — for every slot's own kv_len."""
    from repro.kernels.paged_attention import paged_attention_ref
    from repro.models.layers import attention

    bs, K, G, dh = 8, 2, 3, 16
    q, kp, vp, tables, kv_len = _rand_paged(
        seed=7, S=3, bs=bs, K=K, G=G, dh=dh, kv_lens=[5, 11, 24]
    )
    out = paged_attention_ref(q, kp, vp, tables, kv_len, scale=0.3)
    M = tables.shape[1]
    for s in range(3):
        dense_k = np.asarray(kp)[np.asarray(tables[s])].reshape(M * bs, K, dh)
        dense_v = np.asarray(vp)[np.asarray(tables[s])].reshape(M * bs, K, dh)
        o = attention(
            q[s][None, None],                       # [1, 1, H, dh]
            jnp.asarray(dense_k)[None], jnp.asarray(dense_v)[None],
            scale=0.3,
            positions_q=jnp.asarray([int(kv_len[s]) - 1]),
            kv_len=kv_len[s],
        )
        np.testing.assert_allclose(
            np.asarray(o[0, 0]), np.asarray(out[s]), atol=1e-6
        )


def test_paged_kernel_layer_stacked_pool():
    """The 4-D layer-stacked pool layout (what the serving scan carries) must
    match slicing the layer out by hand, on both ref and interpret kernel."""
    from repro.kernels.paged_attention import (
        paged_attention_pallas,
        paged_attention_ref,
    )

    q, kp, vp, tables, kv_len = _rand_paged(
        seed=11, S=3, bs=8, K=2, G=2, dh=16, kv_lens=[4, 9, 17]
    )
    n_layers = 3
    rng = np.random.default_rng(12)
    kp4 = jnp.asarray(rng.standard_normal((n_layers, *kp.shape)), jnp.float32)
    vp4 = jnp.asarray(rng.standard_normal((n_layers, *vp.shape)), jnp.float32)
    for g, pages in ((0, None), (2, None), (2, 1)):
        want = paged_attention_ref(q, kp4[g], vp4[g], tables, kv_len, scale=0.25)
        got_ref = paged_attention_ref(
            q, kp4, vp4, tables, kv_len, scale=0.25, layer=jnp.int32(g))
        got_ker = paged_attention_pallas(
            q, kp4, vp4, tables, kv_len, scale=0.25, layer=jnp.int32(g),
            pages=pages, interpret=True)
        np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want), atol=2e-6)
        np.testing.assert_allclose(np.asarray(got_ker), np.asarray(want), atol=2e-6)


def test_paged_kernel_output_invariant_to_table_width():
    """Slicing the tables to the live high-water mark (what the server does
    each step) must not change the result: dead entries are masked/skipped."""
    from repro.kernels.paged_attention import paged_attention_ref

    q, kp, vp, tables, kv_len = _rand_paged(
        seed=3, S=2, bs=8, K=2, G=2, dh=16, kv_lens=[6, 14]
    )
    wide = jnp.pad(tables, ((0, 0), (0, 5)))       # extra null-block entries
    a = paged_attention_ref(q, kp, vp, tables, kv_len, scale=0.25)
    b = paged_attention_ref(q, kp, vp, wide, kv_len, scale=0.25)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    from repro.kernels.paged_attention import paged_attention_pallas

    c, d = (paged_attention_pallas(q, kp, vp, t, kv_len, scale=0.25, pages=2,
                                   interpret=True) for t in (tables, wide))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(d))


# ------------------------------------------------------------- scheduler ---


def _mk(rid, arrival=0.0, plen=8, max_new=4):
    return Request(rid=rid, prompt=list(range(plen)), max_new=max_new,
                   arrival=arrival)


def test_scheduler_admission_respects_arrival_and_slots():
    s = Scheduler(ServeConfig(num_slots=2, block_size=8, num_blocks=9,
                              max_blocks_per_slot=4, max_prefills_per_step=4))
    for rid, t in enumerate([0.0, 0.0, 0.0, 5.0]):
        s.submit(_mk(rid, arrival=t))
    adm = s.admit(now=1.0)
    assert [a.rid for a in adm] == [0, 1]          # FIFO, 2 slots
    assert s.allocator.num_held == 2
    # slot eviction refills from the arrived queue, not the future one
    s.requests[0].generated = [1] * 4
    assert s.evict_finished(now=1.5) == [0]
    assert [a.rid for a in s.admit(now=1.5)] == [2]
    s.requests[1].generated = [1] * 4
    assert s.evict_finished(now=2.0) == [1]
    assert s.admit(now=2.0) == []                  # rid 3 hasn't arrived yet
    assert [a.rid for a in s.admit(now=6.0)] == [3]


def test_scheduler_capacity_growth_and_preemption_recompute():
    cfg = ServeConfig(num_slots=2, block_size=4, num_blocks=5,
                      max_blocks_per_slot=4, max_prefills_per_step=4)
    s = Scheduler(cfg)
    s.submit(_mk(0, plen=4, max_new=8))
    s.submit(_mk(1, plen=4, max_new=8))
    adm = s.admit(now=0.0)
    assert len(adm) == 2 and s.allocator.num_free == 2
    for a in adm:
        s.record_token(a.slot, 100 + a.rid, now=0.0)
    # four decode steps take each slot from pos=4 to pos=8: the first step
    # grows both to 2 blocks (pool now empty), pos=8 then wants a third
    for _ in range(4):
        assert s.ensure_capacity() == []
        for slot in s.active_slots():
            s.advance(slot)
            s.record_token(slot, 7, now=0.1)
    assert s.allocator.num_free == 0
    preempted = s.ensure_capacity()
    assert preempted == [1]                        # youngest-admitted victim
    req = s.requests[1]
    assert req.status is RequestStatus.WAITING and req.n_preemptions == 1
    assert req.recompute_prompt == list(range(4)) + [101, 7, 7, 7, 7]
    assert s.waiting[0] == 1                       # requeued at the head
    assert s.allocator.num_held == sum(len(b) for b in s.blocks)
    # survivor kept its blocks and can now grow
    assert 0 in [s.slots[x] for x in s.active_slots()]


def test_preemption_victim_is_youngest_even_if_requesting():
    # rid 0 (older, mid-block, needs no growth) must keep its blocks when the
    # younger rid 1 hits a block boundary on a dry pool: rid 1 preempts itself
    cfg = ServeConfig(num_slots=2, block_size=4, num_blocks=4,
                      max_blocks_per_slot=3, max_prefills_per_step=4)
    s = Scheduler(cfg)
    s.submit(_mk(0, plen=6, max_new=2))    # 2 blocks, pos 6 (mid-block)
    s.submit(_mk(1, plen=4, max_new=4))    # 1 block, pos 4 (boundary)
    adm = s.admit(now=0.0)
    assert len(adm) == 2 and s.allocator.num_free == 0
    assert s.ensure_capacity() == [1]
    assert s.slots.count(None) == 1 and s.requests[0].status is RequestStatus.RUNNING
    assert s.waiting == [1]
    # with the pool freed, the preempted request re-admits and proceeds
    assert [a.rid for a in s.admit(now=0.1)] == [1]


def test_reset_restarts_injected_clock(qwen_serve):
    cfg, params = qwen_serve
    t = [0.0]
    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=2, block_size=8, num_blocks=17, max_blocks_per_slot=4),
        clock=lambda: t[0])
    srv.submit(list(range(2, 10)), 2, arrival=0.0)
    t[0] = 1.5
    srv.drain()
    assert srv.metrics()["wall_s"] == 1.5
    srv.reset()                       # re-times from the injected clock's now
    assert srv.metrics()["wall_s"] == 0.0


def test_scheduler_rejects_infeasible_request():
    s = Scheduler(ServeConfig(num_slots=1, block_size=4, num_blocks=3,
                              max_blocks_per_slot=2))
    with pytest.raises(ValueError):
        s.submit(_mk(0, plen=8, max_new=8))        # needs 4 blocks, cap 2


# ------------------------------------------------ continuous vs static ---


@pytest.mark.parametrize("path", ["paged", "gathered"])
def test_continuous_greedy_matches_static(qwen_serve, path):
    cfg, params = qwen_serve
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist()
               for n in (16, 16, 32, 16)]
    max_new = [6, 3, 5, 4]

    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=2, block_size=8, num_blocks=33, max_blocks_per_slot=6,
        decode_path=path))
    assert srv.decode_path == path
    for p, m in zip(prompts, max_new):
        srv.submit(p, m, arrival=0.0)
    outs = srv.drain()

    ref, ref_met = StaticRunner(cfg, params).run(
        [(p, m, 0.0) for p, m in zip(prompts, max_new)], batch_size=2)
    assert outs == ref
    met = srv.metrics()
    assert met["generated_tokens"] == sum(max_new) == ref_met["generated_tokens"]
    assert met["finished"] == 4 and met["preemptions"] == 0
    # slot refill: mixed budgets on 2 slots must take fewer engine steps than
    # the lockstep equivalent (sum of per-batch maxima)
    assert met["steps"] < 6 + 5 + 2  # static: max(6,3) + max(5,4) + prefills


def test_preemption_recompute_preserves_outputs(qwen_serve):
    """Preemption/refill round trip: the paged no-gather path and the
    gathered-dense oracle must both recompute to token-identical greedy
    streams through block reuse."""
    cfg, params = qwen_serve
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, cfg.vocab_size, size=16).tolist() for _ in range(3)]
    ref, _ = StaticRunner(cfg, params).run(
        [(p, 12, 0.0) for p in prompts], batch_size=3)

    # 8 usable blocks of 8 for three 16+12-token sequences -> must preempt
    for path in ("paged", "gathered"):
        srv = MegaServe(cfg, params, ServeConfig(
            num_slots=3, block_size=8, num_blocks=9, max_blocks_per_slot=4,
            decode_path=path))
        for p in prompts:
            srv.submit(p, 12, arrival=0.0)
        outs = srv.drain()
        assert srv.metrics()["preemptions"] > 0, path
        assert outs == ref, path


def test_paged_kernel_end_to_end_greedy(qwen_serve):
    """Interpret-mode Pallas kernel wired through the full serving loop:
    greedy streams must match the static lockstep engine token-for-token."""
    cfg, params = qwen_serve
    rng = np.random.default_rng(6)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist() for n in (8, 16)]
    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=2, block_size=8, num_blocks=17, max_blocks_per_slot=4,
        decode_path="paged", paged_attn_impl="pallas_interpret"))
    for p in prompts:
        srv.submit(p, 4, arrival=0.0)
    outs = srv.drain()
    ref, _ = StaticRunner(cfg, params).run(
        [(p, 4, 0.0) for p in prompts], batch_size=1)
    assert outs == ref


def test_paged_decode_chunks_counter(qwen_serve):
    """Each paged decode call records ``serve.paged_decode_chunks``: the
    chunks of 256 key rows the decode kernel walks per layer call, summed
    over the active slots.  A 300-token prompt walks 2, a 10-token one 1."""
    from repro.obs import MetricsRegistry

    cfg, params = qwen_serve
    rng = np.random.default_rng(4)
    reg = MetricsRegistry()
    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=2, block_size=8, num_blocks=48, max_blocks_per_slot=40,
        max_prefills_per_step=2, decode_path="paged"), registry=reg)
    for n in (300, 10):
        srv.submit(rng.integers(2, cfg.vocab_size, size=n).tolist(), 3,
                   arrival=0.0)
    srv.drain()
    snap = reg.snapshot()
    chunks = snap["serve.paged_decode_chunks"]
    assert chunks["count"] == snap["serve.decode_step_s"]["count"] > 0
    assert chunks["max"] == 3 and chunks["min"] >= 1


def test_prefill_bucketing_bounds_compile_cache(qwen_serve):
    """Attention-only families right-pad prompts to power-of-two block
    buckets: many distinct prompt lengths share a handful of prefill
    executables, with identical greedy outputs."""
    cfg, params = qwen_serve
    rng = np.random.default_rng(8)
    lens = [3, 5, 9, 11, 14, 17, 23, 30]
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist() for n in lens]
    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=2, block_size=8, num_blocks=33, max_blocks_per_slot=6))
    assert srv._pad_prefill
    for p in prompts:
        srv.submit(p, 3, arrival=0.0)
    outs = srv.drain()
    # 8 distinct lengths spanning 1-4 blocks -> buckets {1, 2, 4} only
    assert set(srv._prefill_cache) <= {1, 2, 4}
    ref, _ = StaticRunner(cfg, params).run(
        [(p, 3, 0.0) for p in prompts], batch_size=1)
    assert outs == ref


def test_decode_path_auto_selection(qwen_serve):
    from repro.core.scope import ProbeSpec, ScopeCollector

    cfg, params = qwen_serve
    scfg = ServeConfig(num_slots=2, block_size=8, num_blocks=17,
                       max_blocks_per_slot=4)
    assert MegaServe(cfg, params, scfg).decode_path == "paged"
    # a live MegaScope collector needs the vmapped per-slot capture
    # semantics -> auto falls back to the gathered oracle
    scope = ScopeCollector(probes=[ProbeSpec("final_hidden", "stats")])
    assert MegaServe(cfg, params, scfg, collector=scope).decode_path == "gathered"
    with pytest.raises(ValueError):
        MegaServe(cfg, params, ServeConfig(
            num_slots=2, block_size=8, num_blocks=17, max_blocks_per_slot=4,
            decode_path="bogus"))


def test_continuous_window_family_griffin():
    """Griffin mixes windowed-attention blocks (paged leaves, window-masked
    kernel) with RG-LRU recurrent blocks (slot-state leaves) — the batched
    paged step must dispatch both correctly."""
    cfg = get_config("recurrentgemma-9b", smoke=True).replace(
        compute_dtype="float32")
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist() for n in (8, 16)]
    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=2, block_size=8, num_blocks=17, max_blocks_per_slot=4))
    assert srv.decode_path == "paged" and not srv._pad_prefill
    kv = srv.kv
    flags = jax.tree.leaves(kv.paged)
    assert any(flags) and not all(flags)     # mixed paged + slot-state
    for p in prompts:
        srv.submit(p, 4, arrival=0.0)
    outs = srv.drain()
    ref, _ = StaticRunner(cfg, params).run(
        [(p, 4, 0.0) for p in prompts], batch_size=1)
    assert outs == ref


def test_continuous_state_family_rwkv():
    cfg = get_config("rwkv6-3b", smoke=True).replace(compute_dtype="float32")
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist() for n in (8, 16)]
    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=2, block_size=8, num_blocks=17, max_blocks_per_slot=4))
    kv = srv.kv
    assert not any(jax.tree.leaves(kv.paged))      # pure slot-state family
    for p in prompts:
        srv.submit(p, 4, arrival=0.0)
    outs = srv.drain()
    ref, _ = StaticRunner(cfg, params).run(
        [(p, 4, 0.0) for p in prompts], batch_size=1)
    assert outs == ref


def test_budget_and_eos_respected_at_prefill(qwen_serve):
    cfg, params = qwen_serve
    rng = np.random.default_rng(4)
    prompt = rng.integers(2, cfg.vocab_size, size=8).tolist()

    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=2, block_size=8, num_blocks=17, max_blocks_per_slot=4))
    rid1 = srv.submit(prompt, 1, arrival=0.0)        # done at prefill
    outs = srv.drain()
    assert len(outs[rid1]) == 1

    # eos emitted by the prefill itself must stop generation immediately
    srv.reset()
    first = outs[rid1][0]
    rid2 = srv.submit(prompt, 10, arrival=0.0, eos_id=first)
    outs = srv.drain()
    assert outs[rid2] == [first]


# ----------------------------------------------------------- integration ---


def test_trace_events_and_scope_captures(qwen_serve):
    from repro.core.scope import ProbeSpec, ScopeCollector

    cfg, params = qwen_serve
    scope = ScopeCollector(probes=[ProbeSpec("final_hidden", "stats")])
    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=2, block_size=8, num_blocks=17, max_blocks_per_slot=4),
        collector=scope)
    rng = np.random.default_rng(3)
    srv.submit(rng.integers(2, cfg.vocab_size, size=8).tolist(), 3, arrival=0.0)
    srv.drain()

    events = srv.trace_events()
    kinds = {e.name for e in events}
    assert {"prefill", "decode"} <= kinds
    for e in events:
        assert e.dur >= 0
        # device calls carry their token count; the tick's host spans
        # (tick, admit, upload, pull) carry none
        if e.kind == "compute":
            assert e.args.get("tokens", 0) >= 1
        else:
            assert e.kind == "host"
    doc = to_chrome(events)                         # MegaScan-compatible
    assert doc["traceEvents"]

    stream = srv.streams[0]
    assert len(stream) == 3
    for item in stream:
        caps = item.captures.get("top", item.captures)
        assert any("final_hidden" in k for k in caps), caps


# -------------------------------------------- speculative decoding ---


@pytest.mark.parametrize("window", [None, 7])
def test_paged_ref_multi_query_matches_per_row(window):
    """q_len > 1 (the spec-decode verify layout) must equal scoring each
    query row separately at its own causal kv_len — causal masking inside
    the query block, window shifted per query."""
    from repro.kernels.paged_attention import paged_attention_ref

    Q = 4
    rng = np.random.default_rng(9)
    q, kp, vp, tables, kv_len = _rand_paged(
        seed=9, S=3, bs=8, K=2, G=2, dh=16, kv_lens=[6, 17, 24]
    )
    q4 = jnp.asarray(rng.standard_normal((3, Q, q.shape[1], 16)), jnp.float32)
    out = paged_attention_ref(q4, kp, vp, tables, kv_len, scale=0.3,
                              window=window)
    assert out.shape == (3, Q, q.shape[1], 16)
    for qi in range(Q):
        row = paged_attention_ref(
            q4[:, qi], kp, vp, tables, kv_len - (Q - 1 - qi), scale=0.3,
            window=window,
        )
        np.testing.assert_allclose(
            np.asarray(out[:, qi]), np.asarray(row), atol=1e-6
        )


@pytest.mark.parametrize("window", [None, 7])
def test_paged_kernel_interpret_multi_query(window):
    from repro.kernels.paged_attention import (
        paged_attention_pallas,
        paged_attention_ref,
    )

    rng = np.random.default_rng(13)
    q, kp, vp, tables, kv_len = _rand_paged(
        seed=13, S=4, bs=8, K=2, G=2, dh=16, kv_lens=[5, 8, 19, 23]
    )
    q4 = jnp.asarray(rng.standard_normal((4, 3, q.shape[1], 16)), jnp.float32)
    ref = paged_attention_ref(q4, kp, vp, tables, kv_len, scale=0.25,
                              window=window)
    ker = paged_attention_pallas(q4, kp, vp, tables, kv_len, scale=0.25,
                                 window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=2e-6)


def test_ngram_drafter_prompt_lookup():
    from repro.serve import NGramDrafter

    d = NGramDrafter(max_ngram=3, min_ngram=1)
    # suffix [7, 8] occurred earlier -> propose what followed it
    assert d.propose([7, 8, 9, 1, 7, 8], 2) == [9, 1]
    assert d.propose([7, 8, 9, 1, 7, 8], 4) == [9, 1, 7, 8]
    # no earlier occurrence of any suffix n-gram -> no proposal
    assert d.propose([1, 2, 3, 4, 5], 3) == []
    assert d.propose([5], 3) == []
    assert d.propose([7, 8, 9], 0) == []
    # the most recent match wins over an older one
    assert d.propose([2, 5, 1, 2, 6, 1, 2], 1) == [6]


def test_scheduler_spec_capacity_and_trim():
    cfg = ServeConfig(num_slots=1, block_size=4, num_blocks=8,
                      max_blocks_per_slot=6, max_prefills_per_step=1)
    s = Scheduler(cfg)
    s.submit(_mk(0, plen=4, max_new=12))
    s.admit(now=0.0)
    assert len(s.blocks[0]) == 1 and s.pos[0] == 4
    # a 4-draft verify writes positions 4..8 -> needs 3 blocks total
    assert s.ensure_capacity({0: 5}) == []
    assert len(s.blocks[0]) == 3
    # only 1 draft accepted (pos -> 6): trim rewinds the high-water mark
    s.advance(0, 2)
    s.trim_blocks()
    assert len(s.blocks[0]) == 2
    assert s.allocator.num_held == 2
    assert list(s.tables[0, 2:]) == [0] * 4


def test_spec_greedy_matches_nonspec_paged(qwen_serve):
    """Speculative greedy streams must be token-identical to plain paged
    decode, while emitting more than one token per accepted verify step."""
    cfg, params = qwen_serve
    rng = np.random.default_rng(21)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist()
               for n in (16, 16, 32, 16)]
    max_new = [12, 6, 10, 8]
    base = dict(num_slots=2, block_size=8, num_blocks=33, max_blocks_per_slot=8)

    srv = MegaServe(cfg, params, ServeConfig(**base))
    for p, m in zip(prompts, max_new):
        srv.submit(p, m, arrival=0.0)
    ref = srv.drain()

    spec = MegaServe(cfg, params, ServeConfig(
        **base, spec_decode=True, spec_k=4))
    assert spec.decode_path == "paged"
    for p, m in zip(prompts, max_new):
        spec.submit(p, m, arrival=0.0)
    outs = spec.drain()
    assert outs == ref
    met = spec.metrics()
    assert met["spec_proposed"] > 0 and met["spec_accepted"] > 0
    # accepted drafts compress engine steps below one-token-per-step
    assert met["steps"] < srv.metrics()["steps"]
    names = {e.name for e in spec.trace_events()}
    assert {"draft", "verify", "accept"} <= names


def test_spec_preemption_roundtrip_preserves_outputs(qwen_serve):
    """Preemption-by-recompute under speculation: the drafter is stateless
    given history, so the recompute path must land on identical streams."""
    cfg, params = qwen_serve
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, cfg.vocab_size, size=16).tolist()
               for _ in range(3)]
    ref, _ = StaticRunner(cfg, params).run(
        [(p, 12, 0.0) for p in prompts], batch_size=3)
    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=3, block_size=8, num_blocks=9, max_blocks_per_slot=4,
        spec_decode=True, spec_k=3))
    for p in prompts:
        srv.submit(p, 12, arrival=0.0)
    outs = srv.drain()
    assert srv.metrics()["preemptions"] > 0
    assert outs == ref


def test_spec_griffin_window_family():
    """Windowed-attention griffin (pattern reduced to attn-only: every cache
    leaf is paged) must speculate through the window-masked multi-query
    kernel path with token-identical greedy streams."""
    from dataclasses import replace as dc_replace

    cfg = get_config("recurrentgemma-9b", smoke=True).replace(
        compute_dtype="float32")
    cfg = cfg.replace(griffin=dc_replace(cfg.griffin, pattern=("attn",)))
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist()
               for n in (8, 16)]
    base = dict(num_slots=2, block_size=8, num_blocks=17,
                max_blocks_per_slot=4)
    srv = MegaServe(cfg, params, ServeConfig(**base))
    assert all(jax.tree.leaves(srv.kv.paged))
    for p in prompts:
        srv.submit(p, 8, arrival=0.0)
    ref = srv.drain()
    spec = MegaServe(cfg, params, ServeConfig(
        **base, spec_decode=True, spec_k=3))
    for p in prompts:
        spec.submit(p, 8, arrival=0.0)
    assert spec.drain() == ref


def test_spec_rejects_state_family_and_gathered(qwen_serve):
    cfg, params = qwen_serve
    rcfg = get_config("rwkv6-3b", smoke=True).replace(compute_dtype="float32")
    rparams = get_model(rcfg).init(rcfg, jax.random.PRNGKey(0))
    scfg = dict(num_slots=2, block_size=8, num_blocks=17,
                max_blocks_per_slot=4, spec_decode=True)
    with pytest.raises(ValueError, match="attention-only"):
        MegaServe(rcfg, rparams, ServeConfig(**scfg))
    with pytest.raises(ValueError, match="paged"):
        MegaServe(cfg, params, ServeConfig(**scfg, decode_path="gathered"))


def test_spec_adversarial_drafter_adapts_off(qwen_serve):
    """A drafter that is always wrong must not change outputs, and the
    per-request draft-length adaptation must shut speculation off (plain
    decode steps resume) instead of burning a verify every tick."""
    from repro.serve import RandomDrafter

    cfg, params = qwen_serve
    rng = np.random.default_rng(11)
    prompt = rng.integers(2, cfg.vocab_size, size=16).tolist()
    base = dict(num_slots=2, block_size=8, num_blocks=33, max_blocks_per_slot=8)
    srv = MegaServe(cfg, params, ServeConfig(**base))
    srv.submit(prompt, 24, arrival=0.0)
    ref = srv.drain()

    spec = MegaServe(cfg, params, ServeConfig(
        **base, spec_decode=True, spec_k=4, spec_retry=64),
        drafter=RandomDrafter(cfg.vocab_size, seed=0))
    rid = spec.submit(prompt, 24, arrival=0.0)
    outs = spec.drain()
    assert outs == {rid: ref[0]}
    req = spec.sched.requests[rid]
    assert req.draft_len == 0                   # adapted off
    met = spec.metrics()
    assert met["spec_accept_rate"] < 0.2
    # after adaptation the engine falls back to plain decode ticks
    assert any(e.name == "decode" for e in spec.trace_events())


def test_spec_eos_mid_acceptance_stops_stream(qwen_serve):
    """An eos inside an accepted draft run must cut the stream exactly at
    the eos, matching the non-speculative path."""
    cfg, params = qwen_serve
    rng = np.random.default_rng(4)
    prompt = rng.integers(2, cfg.vocab_size, size=16).tolist()
    base = dict(num_slots=2, block_size=8, num_blocks=33, max_blocks_per_slot=8)
    srv = MegaServe(cfg, params, ServeConfig(**base))
    rid = srv.submit(prompt, 16, arrival=0.0)
    ref = srv.drain()[rid]
    eos = ref[7]
    want = ref[: ref.index(eos) + 1]

    for spec_on in (False, True):
        s = MegaServe(cfg, params, ServeConfig(
            **base, spec_decode=spec_on, spec_k=4))
        r = s.submit(prompt, 16, arrival=0.0, eos_id=eos)
        assert s.drain()[r] == want, f"spec_decode={spec_on}"


def test_poisson_requests_inclusive_budget_range():
    reqs = poisson_requests(64, rate=100.0, max_new_range=(1, 1), seed=0)
    assert {r.max_new for r in reqs} == {1}
    reqs = poisson_requests(256, rate=100.0, max_new_range=(4, 8), seed=0)
    assert min(r.max_new for r in reqs) >= 4
    assert max(r.max_new for r in reqs) == 8     # upper bound reachable


def test_simkit_serving_policy_comparison():
    reqs = poisson_requests(24, rate=200.0, seed=3)
    eng = Engine()
    cont = serving_throughput(eng.run(
        serving_workload(reqs, policy="continuous", num_slots=4)))
    stat = serving_throughput(eng.run(
        serving_workload(reqs, policy="static", num_slots=4, batch_size=4)))
    assert cont["tokens"] == stat["tokens"] == sum(r.max_new for r in reqs)
    assert cont["tokens_per_s"] > stat["tokens_per_s"]


def test_simkit_serving_respects_arrivals():
    reqs = [RequestSpec(rid=0, arrival=0.5, prompt_len=8, max_new=2),
            RequestSpec(rid=1, arrival=1.0, prompt_len=8, max_new=2)]
    res = Engine().run(serving_workload(reqs, policy="continuous", num_slots=2))
    starts = {r.tid: r.start for r in res.records}
    assert starts["prefill_r0"] >= 0.5
    assert starts["prefill_r1"] >= 1.0
