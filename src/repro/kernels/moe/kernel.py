"""Grouped expert SwiGLU Pallas TPU kernel (dropless, one device's experts).

The rows of ``x`` are routing picks sorted by held expert (``group_sizes``
rows each, then the picks on absent experts, which are not computed).  For
every row tile that holds picks of a group, the kernel runs that group's
whole SwiGLU expert, ``(silu(x W_gate) * (x W_up)) W_down``, tiled over the
expert width ``F``:

    grid (active tile, f)    x tile [tm, D] stays resident across f;
                             W_gate / W_up [D, tf] and W_down [tf, D] of the
                             tile's group stream in; the [tm, D] float32
                             accumulator sums the f tiles.

The weights may come stacked over layers (``[n_layers, G, ...]``) with the
layer index riding scalar prefetch into the index maps, so the kernel reads
its layer's experts where they lie: a per-layer slice passed in would be
copied out of the stack first (and XLA may stage such a copy in VMEM, where
the kernel's reads no longer show what the layer costs in HBM).

The active tiles come from megablox's ``make_group_metadata``: a row tile
that two groups share is visited once per group, consecutively, and each
visit stores only its own group's rows, so the output block stays resident
between the visits.  Empty groups are not visited, so an expert that got no
pick costs nothing, and the expert weights are read about once per call
whatever the number of picks: decode is bound by the bytes of the held
experts, as an expert-parallel deployment's is.  Rows of no held group are
left unwritten; the caller selects only held picks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata


def _expert_kernel(offs_ref, gid_ref, tile_ref, lay_ref,   # scalar prefetch
                   x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, tm: int):
    del lay_ref
    i = pl.program_id(0)
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    acc_ref[...] += jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)

    @pl.when(f == pl.num_programs(1) - 1)
    def _store():
        grp = gid_ref[i]
        row = tile_ref[i] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offs_ref[grp]) & (row < offs_ref[grp + 1])
        o_ref[...] = jnp.where(mine, acc_ref[...],
                               o_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tf", "interpret"))
def expert_ffn_pallas(
    x: jax.Array,         # [m, D] picks, sorted by held expert
    w_gate: jax.Array,    # [(n_layers,) G, D, F]
    w_up: jax.Array,      # [(n_layers,) G, D, F]
    w_down: jax.Array,    # [(n_layers,) G, F, D]
    group_sizes: jax.Array,  # [G] int32
    *,
    tm: int = 128,
    tf: int = 128,
    interpret: bool = False,
    layer: jax.Array | None = None,  # required for layer-stacked weights
) -> jax.Array:
    if w_gate.ndim == 3:  # one layer's experts: lift to the stacked layout
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = jnp.zeros((), jnp.int32)
    m, D = x.shape
    _, G, _, F = w_gate.shape
    tf = tf if F % tf == 0 else F
    pad = (-m) % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    (offs, gids, tiles), n_active = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m + pad, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=G,
        visit_empty_groups=False)
    dt = x.dtype
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_active, F // tf),
        in_specs=[
            pl.BlockSpec((tm, D), lambda i, f, o, g, t, l: (t[i], 0)),
            pl.BlockSpec((None, 1, D, tf),
                         lambda i, f, o, g, t, l: (l[0], g[i], 0, f)),
            pl.BlockSpec((None, 1, D, tf),
                         lambda i, f, o, g, t, l: (l[0], g[i], 0, f)),
            pl.BlockSpec((None, 1, tf, D),
                         lambda i, f, o, g, t, l: (l[0], g[i], f, 0)),
        ],
        out_specs=pl.BlockSpec((tm, D), lambda i, f, o, g, t, l: (t[i], 0)),
        scratch_shapes=[pltpu.VMEM((tm, D), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_expert_kernel, tm=tm),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((m + pad, D), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        # the kernel's name on the device trace, which the benchmark's
        # roofline reader matches
        name="expert_gmm_pallas",
    )(offs, gids, tiles, lay, x, w_gate.astype(dt), w_up.astype(dt),
      w_down.astype(dt))
    return out[:m] if pad else out
