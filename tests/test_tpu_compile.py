"""The serving kernels compile for a TPU v5e at real widths.

Compiles paged decode (Q = 1 and the Q = 5 verify step; at the serving
cell's 64 slots and 256-entry tables, one grid step per slot) and flash prefill
for a v5e chip described through ``jax.experimental.topologies`` — no chip
needed, the TPU compiler is installed beside JAX — at qwen2-0.5b's widths
(14 query / 2 KV heads, head_dim 64) and at 40 / 8 heads, head_dim 128, with
the serving block size 16 and the layer-stacked pool, and MegaServe's own decode and flash prefill steps
at the full width of qwen2-0.5b.  The compiler refuses what interpret mode
accepts: misaligned block tiles, non-2-D matmuls inside a kernel, VMEM
overruns.  At the serving cell's pool size the compiled steps are checked
for copies of the whole KV pool (a relayout the pool's layout must avoid).
The DeepSeek-V2-Lite cell's decode step and 8192-token prefill compile at
the cell's sizes and must fit the chip's memory.

The topology is described inside fixtures only: only one process at a time
may load the TPU library, so describing it while a module is imported would
break multi-worker test runs.
"""

import math
import os
import re

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
    pool = sds((LAYERS, BLOCKS, BS, K * dh), jnp.bfloat16)
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


def _pallas_grids(jaxpr) -> list[tuple]:
    """The grids of every ``pallas_call`` in ``jaxpr``, nested ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(tuple(eqn.params["grid_mapping"].grid))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                out += _pallas_grids(getattr(inner, "jaxpr", inner))
    return out


@pytest.mark.parametrize("Q", [1, 4])
@pytest.mark.parametrize("width", [64, 256])
def test_paged_decode_compiles_for_v5e_at_serving_cell(one_chip, width, Q):
    """At the qwen2-0.5b serving cell's shapes (64 slots, 14 / 2 heads,
    head_dim 64, blocks of 16, the 24-layer stacked pool) the decode kernel
    compiles for a v5e at a 64- and a 256-entry table, and its grid is one
    step per slot whatever the table's width: the table entries are walked
    inside the step, live ones only."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, H, K, dh = 64, 14, 2, 64
    pool = sds((24, 2049, BS, K * dh), jnp.bfloat16)
    args = (sds((S, Q, H, dh), jnp.bfloat16), pool, pool,
            sds((S, width), jnp.int32), sds((S,), jnp.int32),
            sds((), jnp.int32))

    def fn(q, kp, vp, t, n, lay):
        return paged_attention_pallas(q, kp, vp, t, n, scale=dh ** -0.5,
                                      layer=lay)

    assert _pallas_grids(jax.make_jaxpr(fn)(*args).jaxpr) == [(S,)]
    assert "tpu_custom_call" in _compiled_text(fn, args)


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


def _served_step_text(one_chip, step, *, slots, blocks, width, table=None):
    """MegaServe's own ``step`` ("decode" at a ``table``-wide block table,
    or the ``width``-block flash prefill bucket) at the full width of
    qwen2-0.5b, compiled for the described chip; returns the compiled text
    and the stacked KV pool as it sits on the chip."""
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

    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=slots, block_size=BS, num_blocks=blocks,
        max_blocks_per_slot=width, paged_attn_impl="pallas",
        prefill_path="flash"))
    pa, pool = on_chip(params), on_chip(srv.pool)
    if step == "decode":
        t = table or width
        lowered = srv._decode_jit.lower(
            pa, pool, i32(slots, t), i32(slots), i32(slots))
    else:
        lowered = srv._build_prefill_jit(width).lower(
            pa, i32(1, width * BS), i32(), pool, i32(), i32(width))
    return lowered.compile().as_text(), pool


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_served_steps_compile_for_v5e_at_qwen2_width(one_chip, step):
    """MegaServe's own decode step and its 512-token flash prefill bucket at
    the full width of qwen2-0.5b: the served prefill must reach the flash
    kernel (a decode kernel at Q = 512 overruns VMEM).  The compiled program
    carries the names a device trace shows: the module ``jit_serve_<step>``
    and the Pallas kernel it runs."""
    S, width = 4, 32
    txt, _ = _served_step_text(
        one_chip, step, slots=S, blocks=S * width + 1, width=width)
    assert "tpu_custom_call" in txt
    assert f"HloModule jit_serve_{step}" in txt
    kernel = {"decode": "paged_attention_pallas",
              "prefill": "paged_prefill_pallas"}[step]
    assert kernel in txt


_COPY = re.compile(r"=\s*(.*?)\s(?:copy|copy-start)\(")


def _whole_pool_copies(txt: str, pool_elems: int) -> list[str]:
    """The ``copy`` / ``copy-start`` instructions of a compiled program that
    produce an array of as many elements as the whole stacked pool leaf, in
    whatever shape (a relayout of the pool, or of a view of it)."""
    out = []
    for line in txt.splitlines():
        m = _COPY.search(line)
        if m and any(
            math.prod(int(d) for d in dims.split(",") if d) == pool_elems
            for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))
        ):
            out.append(line.strip()[:160])
    return out


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_served_steps_copy_no_whole_pool_on_v5e(one_chip, step):
    """At the serving cell's pool (2049 blocks of 16, 64 slots, qwen2-0.5b)
    the compiled decode step and 4096-token flash prefill bucket copy no
    whole pool: the pool is stored as ``[n, blocks, bs, K*dh]``, the tile
    the kernels read, so neither the layer loop's scatters nor the kernel
    calls relayout it.  A pool stored ``[.., K, dh]`` and reshaped for the
    kernels costs 8 such copies in decode (4 inside the layer loop) and 4 in
    this prefill."""
    txt, pool = _served_step_text(
        one_chip, step, slots=64, blocks=2049, width=256, table=32)
    elems = {math.prod(leaf.shape) for leaf in jax.tree.leaves(pool)}
    assert elems == {24 * 2049 * 16 * 2 * 64}
    assert f"HloModule jit_serve_{step}" in txt
    assert _whole_pool_copies(txt, elems.pop()) == []


@pytest.fixture(scope="module")
def deepseek_cell_server():
    """MegaServe for the ``serve-deepseek-v2-lite-ep8-chat`` cell's shapes:
    DeepSeek-V2-Lite with 8 of its 64 routed experts held, 128 slots, tables
    of up to 560 blocks of 16, bfloat16 weights as shapes only."""
    from repro.configs import MoEConfig, get_config
    from repro.models import lm
    from repro.serve import MegaServe, ServeConfig

    base = get_config("deepseek-v2-lite-16b")
    cfg = base.replace(moe=MoEConfig(**{**base.moe.__dict__,
                                         "experts_held": 8}))
    params = jax.eval_shape(lambda: lm.init(cfg, jax.random.PRNGKey(0)))
    srv = MegaServe(cfg, params, ServeConfig(
        num_slots=128, block_size=BS, num_blocks=9, max_blocks_per_slot=560,
        paged_attn_impl="pallas", prefill_path="flash"))
    return srv, params


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_deepseek_cell_steps_compile_and_fit_v5e(one_chip, deepseek_cell_server,
                                                monkeypatch, step):
    """The served decode step (128 slots, the widest table) and the
    8192-token prefill bucket of the DeepSeek-V2-Lite cell compile for a
    v5e with the cell's 14000-block latent pool, reach the latent decode
    kernel and the grouped expert kernel, and fit the chip's 16 GB with more
    than 1 GB to spare: 6.22 GB of weights, 7.74 GB of pool, and the step's
    workspace."""
    srv, params = deepseek_cell_server

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pa = jax.tree.map(lambda x: on_chip(x.shape, jnp.bfloat16), params)
    pool = jax.tree.map(
        lambda x: on_chip((x.shape[0], 14000, *x.shape[2:]), jnp.bfloat16),
        srv.pool)
    i32 = lambda *s: on_chip(s, jnp.int32)  # noqa: E731
    # the steps pick their kernels by the backend; this process's is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if step == "decode":
        lowered = srv._decode_jit.lower(pa, pool, i32(128, 560), i32(128),
                                        i32(128))
        kernels = {"paged_latent_attention_pallas", "expert_gmm_pallas"}
    else:
        lowered = srv._build_prefill_jit(512).lower(
            pa, i32(1, 512 * BS), i32(), pool, i32(), i32(512))
        kernels = {"expert_gmm_pallas"}
    compiled = lowered.compile()
    txt = compiled.as_text()
    assert all(k in txt for k in kernels)
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total < 15e9, total
