"""The serving kernels compile for a TPU v5e at real widths.

Compiles paged decode (Q = 1 and the Q = 5 verify step) and flash prefill
for a v5e chip described through ``jax.experimental.topologies`` — no chip
needed, the TPU compiler is installed beside JAX — at qwen2-0.5b's widths
(14 query / 2 KV heads, head_dim 64) and at 40 / 8 heads, head_dim 128, with
the serving block size 16 and the layer-stacked pool, and MegaServe's own decode and flash prefill steps
at the full width of qwen2-0.5b.  The compiler refuses what interpret mode
accepts: misaligned block tiles, non-2-D matmuls inside a kernel, VMEM
overruns.

The topology is described inside fixtures only: only one process at a time
may load the TPU library, so describing it while a module is imported would
break multi-worker test runs.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import (
    paged_attention_pallas,
    paged_prefill_pallas,
)

WIDTHS = [(14, 2, 64), (40, 8, 128)]   # (query heads, kv heads, head_dim)
LAYERS, BLOCKS, BS, M = 3, 64, 16, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _pool_args(sds, K, dh, S):
    pool = sds((LAYERS, BLOCKS, BS, K, dh), jnp.bfloat16)
    return (pool, pool, sds((S, M), jnp.int32), sds((S,), jnp.int32),
            sds((), jnp.int32))


def _compiled_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("Q", [1, 5])
@pytest.mark.parametrize("H,K,dh", WIDTHS)
def test_paged_decode_compiles_for_v5e(one_chip, H, K, dh, Q):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S = 4
    args = (sds((S, Q, H, dh), jnp.bfloat16), *_pool_args(sds, K, dh, S))
    txt = _compiled_text(
        lambda q, kp, vp, t, n, lay: paged_attention_pallas(
            q, kp, vp, t, n, scale=dh ** -0.5, layer=lay),
        args,
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("H,K,dh", WIDTHS)
def test_flash_prefill_compiles_for_v5e(one_chip, H, K, dh):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, Q = 2, 128
    args = (sds((S, Q, H, dh), jnp.bfloat16), *_pool_args(sds, K, dh, S),
            sds((dh,), jnp.bfloat16))
    txt = _compiled_text(
        lambda q, kp, vp, t, n, lay, qn: paged_prefill_pallas(
            q, kp, vp, t, n, scale=dh ** -0.5, layer=lay, q_norm=qn,
            rope_theta=1e6, q_block=32),
        args,
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_served_steps_compile_for_v5e_at_qwen2_width(one_chip, step):
    """MegaServe's own decode step and its 512-token flash prefill bucket at
    the full width of qwen2-0.5b: the served prefill must reach the flash
    kernel (a decode kernel at Q = 512 overruns VMEM)."""
    from repro.configs import get_config
    from repro.models import get_model
    from repro.serve import MegaServe, ServeConfig

    cfg = get_config("qwen2-0.5b")
    params = jax.eval_shape(
        lambda: get_model(cfg).init(cfg, jax.random.PRNGKey(0)))

    def on_chip(tree):
        # bf16 as on the TPU (the CPU backend widens the pool to f32)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, jnp.bfloat16 if x.dtype == jnp.float32 else x.dtype,
            sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    S, width = 4, 32
    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=S, block_size=BS, num_blocks=S * width + 1,
        max_blocks_per_slot=width, paged_attn_impl="pallas",
        prefill_path="flash"))
    pa, pool = on_chip(params), on_chip(srv.pool)
    if step == "decode":
        lowered = srv._decode_jit.lower(pa, pool, i32(S, width), i32(S), i32(S))
    else:
        lowered = srv._build_prefill_jit(width).lower(
            pa, i32(1, width * BS), i32(), pool, i32(), i32(width))
    txt = lowered.compile().as_text()
    assert "tpu_custom_call" in txt
    if step == "prefill":
        assert "paged_prefill_pallas" in txt
