"""Random weights of a dense decoder, made on the device from the seed.

The tree has the layout the program's dense family takes (``embedding``,
``final_norm``, and the layer stack ``seg0/b0/...`` with a leading layer
axis).  The values are the benchmark's own: normal with the usual 1/sqrt(fan
in) scale, output projections scaled by 1/sqrt(2 L), and norm scales and
biases drawn around 1 and 0 rather than set to them, so that a path that
drops a scale or a bias shows in the comparison with the reference.  The
program and the reference both read these same arrays.
"""

from __future__ import annotations

import math

import numpy as np

from benchlib.flops import Dense


def _leaves(m: Dense) -> list[tuple[tuple[str, ...], tuple[int, ...], str, float]]:
    """(path, shape, kind, scale) of every leaf, in a fixed order."""
    L, D, H, K, dh, F = (m.n_layers, m.d_model, m.n_heads, m.n_kv_heads,
                         m.head_dim, m.d_ff)
    proj = 1.0 / math.sqrt(2 * L)
    leaves = [
        (("embedding",), (m.vocab_padded, D), "normal", 1.0 / math.sqrt(D)),
        (("final_norm", "scale"), (D,), "scale", 0.1),
        (("seg0", "b0", "ln1", "scale"), (L, D), "scale", 0.1),
        (("seg0", "b0", "ln2", "scale"), (L, D), "scale", 0.1),
        (("seg0", "b0", "attn", "wq"), (L, D, H, dh), "normal", 1.0 / math.sqrt(D)),
        (("seg0", "b0", "attn", "wk"), (L, D, K, dh), "normal", 1.0 / math.sqrt(D)),
        (("seg0", "b0", "attn", "wv"), (L, D, K, dh), "normal", 1.0 / math.sqrt(D)),
        (("seg0", "b0", "attn", "wo"), (L, H, dh, D), "normal",
         proj / math.sqrt(H * dh)),
        (("seg0", "b0", "mlp", "w_gate"), (L, D, F), "normal", 1.0 / math.sqrt(D)),
        (("seg0", "b0", "mlp", "w_up"), (L, D, F), "normal", 1.0 / math.sqrt(D)),
        (("seg0", "b0", "mlp", "w_down"), (L, F, D), "normal",
         proj / math.sqrt(F)),
    ]
    if m.qkv_bias:
        leaves += [
            (("seg0", "b0", "attn", "bq"), (L, H, dh), "normal", 0.1),
            (("seg0", "b0", "attn", "bk"), (L, K, dh), "normal", 0.1),
            (("seg0", "b0", "attn", "bv"), (L, K, dh), "normal", 0.1),
        ]
    return leaves


def seed_key(seed: int):
    """A JAX key from any non-negative integer seed (wider than 32 bits)."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def make_weights(m: Dense, seed: int, dtype: str):
    """The weight tree, made on the default device in one jitted call."""
    import jax
    import jax.numpy as jnp

    spec = _leaves(m)
    dt = jnp.dtype(dtype)

    def build(key):
        keys = jax.random.split(key, len(spec))
        tree: dict = {}
        for k, (path, shape, kind, scale) in zip(keys, spec):
            x = jax.random.normal(k, shape, jnp.float32) * scale
            if kind == "scale":
                x = 1.0 + x
            if m.vocab_padded != m.vocab and path == ("embedding",):
                x = x.at[m.vocab:].set(0.0)
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x.astype(dt)
        return tree

    return jax.jit(build)(seed_key(seed))
