"""XLA reference / fallback for the grouped expert SwiGLU (``kernel.py``).

``x`` holds the routing picks sorted by expert, the rows of held expert ``g``
being ``[sum(sizes[:g]), sum(sizes[:g + 1]))``; rows past ``sum(sizes)``
(picks on experts this device does not hold) come out zero.  Each group's
SwiGLU runs over every row and keeps its own rows: G times the work of the
kernel, but plain, exact and batchable (the gathered decode path vmaps it
over slots, which ``jax.lax.ragged_dot`` does not allow).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def expert_ffn_ref(
    x: jax.Array,         # [m, D] picks, sorted by held expert
    w_gate: jax.Array,    # [(n_layers,) G, D, F]
    w_up: jax.Array,      # [(n_layers,) G, D, F]
    w_down: jax.Array,    # [(n_layers,) G, F, D]
    group_sizes: jax.Array,  # [G] int32 rows per held expert
    layer: jax.Array | None = None,  # indexes layer-stacked weights
) -> jax.Array:
    if layer is not None:
        w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
    dt = x.dtype
    f32 = jnp.float32
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(x.shape[0])[:, None]
    y = jnp.zeros(x.shape, f32)
    for g in range(w_gate.shape[0]):
        gt = jnp.dot(x, w_gate[g].astype(dt), preferred_element_type=f32)
        up = jnp.dot(x, w_up[g].astype(dt), preferred_element_type=f32)
        h = (jax.nn.silu(gt) * up).astype(dt)
        out = jnp.dot(h, w_down[g].astype(dt), preferred_element_type=f32)
        mine = (row >= ends[g] - group_sizes[g]) & (row < ends[g])
        y = jnp.where(mine, out, y)
    return y.astype(dt)
