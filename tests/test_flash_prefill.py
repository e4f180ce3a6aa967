"""Flash-prefill: the banded online-softmax Pallas kernel vs the jnp oracle
(GQA ratios, windows, ragged lengths, chunk-boundary starts, verify widths),
the served token-identity guarantees (flash vs dense, chunked, speculative,
preemption), the recurrent-family pow2-segment prefill driver, and the
MegaServe/compile-cache precompile integration."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels.paged_attention import (
    paged_attention_ref,
    paged_prefill,
)
from repro.models import get_model
from repro.models.layers import apply_rope, rms_head_norm
from repro.serve import MegaServe, ServeConfig
from repro.serve.paged_cache import pow2_segments
from repro.serve.server import StaticRunner

# ------------------------------------------------------------- kernel ---


def _prefill_case(S, Q, H, K, dh, bs, M, kv_lens, *, window=None,
                  qk_norm=False, q_start=None, layered=False, seed=0):
    """Run one (xla oracle, interpret-mode pallas) pair and return
    (o_xla, o_pallas, o_fulltable) plus the scattered pools for comparison."""
    rng = np.random.default_rng(seed)
    n_blocks = 40
    # the pool as PagedKVCache stores it: heads flattened, [.., bs, K*dh]
    shape = ((3,) if layered else ()) + (n_blocks, bs, K * dh)
    k_pool = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    tbl = np.zeros((S, M), np.int32)
    nxt = 1
    for s in range(S):  # distinct physical blocks per slot
        for j in range(min(-(-int(kv_lens[s]) // bs), M)):
            tbl[s, j] = nxt
            nxt += 1
    tables = jnp.asarray(tbl)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, Q, H, dh)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((S, Q, K, dh)), jnp.float32)
    vv = jnp.asarray(rng.standard_normal((S, Q, K, dh)), jnp.float32)
    positions = kv_len[:, None] - Q + jnp.arange(Q)[None, :]
    qn = jnp.asarray(rng.standard_normal(dh), jnp.float32) if qk_norm else None
    kn = jnp.asarray(rng.standard_normal(dh), jnp.float32) if qk_norm else None
    layer = jnp.asarray(1, jnp.int32) if layered else None
    scale = 1.0 / np.sqrt(dh)
    kw = dict(tables=tables, positions=positions, block_size=bs, scale=scale,
              window=window, layer=layer, q_norm=qn, k_norm=kn,
              rope_theta=10000.0, q_start=q_start, q_block=8)
    o_x, c_x = paged_prefill(q, kk, vv, k_pool, v_pool, impl="xla", **kw)
    o_p, c_p = paged_prefill(q, kk, vv, k_pool, v_pool,
                             impl="pallas_interpret", **kw)
    # both impls must write identical K/V into the pool
    np.testing.assert_array_equal(np.asarray(c_x["k"]), np.asarray(c_p["k"]))
    np.testing.assert_array_equal(np.asarray(c_x["v"]), np.asarray(c_p["v"]))
    # unbanded full-table oracle over the *scattered* pool
    qq = q if qn is None else rms_head_norm(qn, q, 1e-6)
    qq = apply_rope(qq, positions, 10000.0)
    o_full = paged_attention_ref(qq, c_x["k"], c_x["v"], tables, kv_len,
                                 scale=scale, window=window, layer=layer)
    return o_x, o_p, o_full


def _check(o_x, o_p, o_full):
    # pallas (online softmax) vs banded oracle: fp32 accumulation noise
    assert float(jnp.abs(o_x - o_p).max()) < 2e-5
    # banded oracle vs full-table oracle: reduction-tree reassociation only
    assert float(jnp.abs(o_x - o_full).max()) < 2e-6


@pytest.mark.parametrize("gqa", [1, 2, 4])
def test_prefill_kernel_full_prompt_gqa(gqa):
    """Full prefill (q_start=0) across GQA ratios H/K in {1, 2, 4}."""
    _check(*_prefill_case(1, 64, 4, 4 // gqa, 16, 16, 6, [64], q_start=0))


def test_prefill_kernel_fused_qk_norm_rope():
    """The kernel's fused rmsnorm+rope q-prologue must match the unfused
    jnp chain bit-for-bit through the same dtype requantization."""
    _check(*_prefill_case(1, 64, 8, 2, 16, 16, 6, [64], q_start=0,
                          qk_norm=True))


def test_prefill_kernel_chunk_boundary_start():
    """Chunked prefill: queries land mid-sequence (cache_len=48 already
    filled, dynamic q_start) and must attend to the prior chunks' blocks."""
    _check(*_prefill_case(1, 32, 4, 2, 16, 16, 8, [32 + 48]))


def test_prefill_kernel_verify_width_ragged_layered():
    """The spec-verify shape: S slots, Q=spec_k+1=5, ragged kv_len across
    slots (7/33/100), layered pool indexing."""
    _check(*_prefill_case(3, 5, 4, 2, 16, 16, 8, [7, 33, 100], layered=True))


@pytest.mark.parametrize("case", [
    dict(S=1, Q=64, H=4, K=2, dh=16, bs=16, M=6, kv_lens=[64], window=24,
         q_start=0),
    dict(S=2, Q=5, H=4, K=2, dh=16, bs=16, M=8, kv_lens=[40, 90], window=16,
         layered=True),
])
def test_prefill_kernel_window_mask(case):
    """Sliding-window masking inside the causal band, both full-prefill and
    verify-width shapes."""
    kv_lens = case.pop("kv_lens")
    args = (case.pop("S"), case.pop("Q"), case.pop("H"), case.pop("K"),
            case.pop("dh"), case.pop("bs"), case.pop("M"), kv_lens)
    _check(*_prefill_case(*args, **case))


# ------------------------------------------------------ served identity ---


@pytest.fixture(scope="module")
def qwen_serve():
    cfg = get_config("qwen2-0.5b", smoke=True).replace(
        compute_dtype="float32", attn_kv_chunk=4096
    )
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _drain(cfg, params, prompts, max_new=8, **scfg_kw):
    kw = dict(num_slots=4, block_size=16, num_blocks=40,
              max_blocks_per_slot=8, decode_path="paged")
    kw.update(scfg_kw)
    srv = MegaServe(cfg, params, ServeConfig(**kw))
    for p in prompts:
        srv.submit(p, max_new)
    return srv.drain(), srv


def test_flash_prefill_token_identity(qwen_serve):
    """Kernel on vs off: flash prefill must be greedy token-identical to the
    dense-prefill path on ragged prompt lengths (incl. non-block-multiples),
    and auto must resolve per backend: flash only where the Pallas kernel
    is real (TPU, or paged_attn_impl forcing it), dense on the CPU oracle
    path where one-shot dense prefill wins."""
    cfg, params = qwen_serve
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (5, 17, 33, 64)]
    dense, _ = _drain(cfg, params, prompts, prefill_path="dense")
    flash, srv = _drain(cfg, params, prompts, prefill_path="flash")
    assert flash == dense
    _, auto = _drain(cfg, params, prompts[:1], prefill_path="auto")
    expect = "flash" if jax.default_backend() == "tpu" else "dense"
    assert auto.prefill_path == expect
    _, forced = _drain(cfg, params, prompts[:1], prefill_path="auto",
                       paged_attn_impl="pallas_interpret")
    assert forced.prefill_path == "flash"


def test_flash_prefill_chunked_and_spec_identity(qwen_serve):
    """The one kernel serves all three entry shapes: full prefill, chunked
    prefill (q_start > 0), and the Q=spec_k+1 verify step — all greedy
    token-identical to the dense baseline."""
    cfg, params = qwen_serve
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (5, 17, 33, 64)]
    dense, _ = _drain(cfg, params, prompts, prefill_path="dense")
    chunked, _ = _drain(cfg, params, prompts, prefill_path="flash",
                        chunked_prefill=True)
    assert chunked == dense
    spec, srv = _drain(cfg, params, prompts, prefill_path="flash",
                       spec_decode=True)
    assert spec == dense
    assert srv.metrics()["spec_accepted"] > 0


def test_flash_prefill_preemption_identity(qwen_serve):
    """Preempt/recompute round trip through the flash path: recomputed
    prefills re-enter through the kernel and must preserve the stream."""
    cfg, params = qwen_serve
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, cfg.vocab_size, size=16).tolist()
               for _ in range(3)]
    ref, _ = StaticRunner(cfg, params).run(
        [(p, 12, 0.0) for p in prompts], batch_size=3)
    # 8 usable blocks of 8 for three 16+12-token sequences -> must preempt
    outs, srv = _drain(cfg, params, prompts, max_new=12, num_slots=3,
                       block_size=8, num_blocks=9, max_blocks_per_slot=4,
                       prefill_path="flash")
    assert srv.metrics()["preemptions"] > 0
    assert outs == ref


def test_flash_requires_paged_pool(qwen_serve):
    """Explicit prefill_path=flash on the gathered decode path (no paged
    pool to walk) must fail loudly, not silently fall back."""
    cfg, params = qwen_serve
    with pytest.raises(ValueError, match="flash"):
        MegaServe(cfg, params, ServeConfig(
            num_slots=2, block_size=16, num_blocks=20, max_blocks_per_slot=4,
            decode_path="gathered", prefill_path="flash"))


# ------------------------------------------------- recurrent seg prefill ---


def test_pow2_segments():
    assert pow2_segments(13) == [8, 4, 1]
    assert pow2_segments(1) == [1]
    assert pow2_segments(64) == [64]
    assert sum(pow2_segments(100)) == 100
    with pytest.raises(ValueError):
        pow2_segments(0)


@pytest.mark.parametrize("arch", ["rwkv6-3b", "recurrentgemma-9b"])
def test_recurrent_seg_prefill_identity(arch):
    """State families prefill through the descending pow2-segment driver;
    streams must match the exact one-shot prefill, and the compiled-driver
    key set must stay one-per-distinct-length (widths are shared)."""
    cfg = get_config(arch, smoke=True).replace(compute_dtype="float32")
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (5, 13, 17)]

    def run(seg_on):
        srv = MegaServe(cfg, params, ServeConfig(
            num_slots=2, block_size=8, num_blocks=24, max_blocks_per_slot=4))
        if seg_on:
            assert srv._seg_ok, "seg driver must be on for state families"
        else:  # exact one-shot dense prefill as the oracle
            srv._seg_ok = False
            srv._prefill_cache.clear()
        for p in prompts:
            srv.submit(p, 6)
        return srv.drain(), len(srv._prefill_cache)

    exact, _ = run(False)
    seg, nkeys = run(True)
    assert seg == exact
    assert nkeys == 3  # one driver per distinct prompt length


# ------------------------------------------- precompile + compile cache ---


def test_precompile_report_and_warm_cache(qwen_serve, tmp_path):
    """precompile() returns per-path {count, ms}; against a CompileCache a
    second engine replays every bucket as a hit (0 misses) and the served
    streams stay identical with and without the cache."""
    from repro.core.compile_cache import CompileCache

    cfg, params = qwen_serve
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (5, 17, 33)]
    scfg = ServeConfig(num_slots=2, block_size=8, num_blocks=24,
                       max_blocks_per_slot=8, decode_path="paged",
                       chunked_prefill=True, chunk_len=16)

    def serve(cache):
        srv = MegaServe(cfg, params, scfg, compile_cache=cache)
        rep = srv.precompile()
        for p in prompts:
            srv.submit(p, 6)
        return srv.drain(), rep

    out_cold, rep_cold = serve(CompileCache(tmp_path))
    for path in ("decode", "prefill", "chunk"):
        assert rep_cold[path]["count"] > 0 and rep_cold[path]["ms"] > 0
    assert rep_cold["verify"]["count"] == 0  # spec off
    assert rep_cold["total"] == sum(
        rep_cold[p]["count"] for p in ("decode", "prefill", "chunk", "verify"))
    assert rep_cold["cache"]["puts"] > 0 and rep_cold["cache"]["hits"] == 0

    out_warm, rep_warm = serve(CompileCache(tmp_path))
    assert rep_warm["cache"]["hits"] == rep_cold["cache"]["puts"]
    assert rep_warm["cache"]["misses"] == 0
    out_ref, _ = serve(None)
    assert out_cold == out_warm == out_ref
