"""Paged KV cache: a fixed-size physical block pool + per-slot block tables.

The static serving path allocates one dense ``[B, cache_len, ...]`` cache, so
every slot pays for the longest sequence it might ever hold.  Here the time
axis of each attention cache leaf is cut into fixed-size blocks that live in
one shared physical pool; a slot owns an ordered *block table* of pool
indices, and slots with wildly different lengths share the pool densely.

Layout convention (matches ``lm.init_cache``): every cache leaf is stacked
over layers exactly once, i.e. shaped ``[n_layers, batch, ...]``.  Leaves
whose post-batch axis is the full-length ``kv_time`` axis (k/v, ckv/kpe,
griffin window k/v) are *paged*:

    dense leaf  [n, B, L_max, *feat]   ->   pool [n, num_blocks, bs, F]

with ``F = prod(feat)``: the trailing feature axes are stored flattened
(``K*dh`` for GQA k/v; MLA's one-axis ``ckv``/``kpe`` keep their shape).
That is the ``(bs, K*dh)`` tile the paged kernels DMA per table entry, so
the pool is stored as the kernels read it: a pool kept as ``[.., K, dh]``
and reshaped at each kernel call is a relayout of the whole pool on the TPU
(a ``[K, dh]`` minor tile pads a head size of 64 to 128 lanes), paid in
every layer of every step.  The dense views (``gather``, the inputs of the
prefill / decode scatters, ``export_slot`` bundles) keep the model's
``[.., *feat]`` axes.

All other leaves (rwkv wkv/x_prev, griffin conv/h — O(1) recurrent state per
slot, nothing to page) are *slot-state* leaves stored densely per slot:

    state leaf  [n, B, *feat]          ->   pool [n, num_slots, *feat]

Block 0 is reserved as the *null block*: padding entries of every block table
point at it, so the decode-path scatter of inactive slots lands there
harmlessly and gathered positions beyond a slot's ``kv_len`` are masked out
by attention anyway.

Two decode paths share this pool (``server.ServeConfig.decode_path``):

* **paged** (default): no dense view is ever built — the paged-attention
  kernel walks each slot's block table directly against the pool and the new
  token's K/V are written in place into the owning block
  (``layers.gqa_apply`` paged branch / ``engine.make_paged_decode_step``);
* **gathered** (the correctness oracle, and the MegaScope deep-probe path):
  gather -> step -> scatter-touched-block — one decode step writes a single
  position per slot, so only the block containing that position goes back to
  the pool.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.serve.engine import cache_axes


class PoolExhausted(RuntimeError):
    """No free physical blocks — the scheduler should preempt."""


class BlockAllocator:
    """Free-list allocator over ``num_blocks`` fixed-size physical blocks.

    Block ids ``[reserved, num_blocks)`` are allocatable; ``[0, reserved)``
    (the null block) never leave the allocator.
    """

    def __init__(self, num_blocks: int, reserved: int = 1):
        if num_blocks <= reserved:
            raise ValueError(f"need > {reserved} blocks, got {num_blocks}")
        self.num_blocks = num_blocks
        self.reserved = reserved
        # LIFO free list: recently-freed blocks are reused first (warm)
        self._free: list[int] = list(range(num_blocks - 1, reserved - 1, -1))
        self._held: set[int] = set()

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_held(self) -> int:
        return len(self._held)

    def alloc(self, n: int = 1) -> list[int]:
        if n > len(self._free):
            raise PoolExhausted(f"want {n} blocks, {len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        self._held.update(out)
        return out

    def try_alloc(self, n: int = 1) -> list[int] | None:
        if n > len(self._free):
            return None
        return self.alloc(n)

    def free(self, blocks: list[int]) -> None:
        for b in blocks:
            if b not in self._held:
                raise ValueError(f"block {b} not held (double free?)")
            self._held.remove(b)
            self._free.append(b)


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` cache positions."""
    return -(-n_tokens // block_size)


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= ``n`` — the jit-compile-cache bucketing used
    for prefill cache lengths and the decode-table high-water mark, so the
    number of compiled shapes stays O(log max_len) under Poisson workloads."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def pow2_segments(n: int) -> list[int]:
    """Descending binary decomposition of ``n`` (13 -> [8, 4, 1]): the exact
    segment widths the recurrent-family prefill driver runs, so any prompt
    length is covered by O(log n) power-of-two segment executables instead of
    one compile per exact length."""
    if n <= 0:
        raise ValueError(f"need n >= 1, got {n}")
    return [1 << b for b in range(n.bit_length() - 1, -1, -1) if n >> b & 1]


@dataclass(frozen=True)
class PoolSpec:
    num_slots: int
    num_blocks: int          # physical blocks incl. the reserved null block
    block_size: int
    max_blocks: int          # block-table width per slot

    @property
    def max_len(self) -> int:
        return self.max_blocks * self.block_size


class PagedKVCache:
    """The physical pool pytree + pure gather/scatter transforms.

    ``self.pool`` mirrors the model's cache treedef; methods are pure in the
    pool (take + return it) so the server can fold them into jitted steps.
    """

    def __init__(self, cfg: ModelConfig, spec: PoolSpec, *,
                 promote_store: bool = False):
        """``promote_store`` widens bfloat16 *paged* leaves to float32
        containers (values are still quantized through bfloat16 on every
        write, so numerics are bit-identical to a bf16 pool).  The in-place
        paged decode path needs this on CPU: XLA CPU cannot alias bf16
        scatters, so a bf16 pool would silently copy itself every step."""
        self.cfg = cfg
        self.spec = spec
        L = spec.max_len
        template = jax.eval_shape(lambda: lm.init_cache(cfg, 1, L))
        axes = cache_axes(template)

        def is_paged(leaf, ax) -> bool:
            # ax comes from cache_axes with the "layers" prefix included
            n_layers = sum(1 for a in ax if a == "layers")
            assert n_layers == 1 and ax[1] == "batch", (
                f"expected [layers, batch, ...], got {leaf.shape} axes {ax}"
            )
            if "kv_time" not in ax:
                return False
            return leaf.shape[ax.index("kv_time")] == L

        self.paged = jax.tree.map(is_paged, template, axes)
        # the dense cache's leaf shapes: a paged leaf's feature axes are
        # ``shape[3:]`` here, flattened into the pool's last axis
        self.dense = template

        def make_pool(leaf, paged):
            n = leaf.shape[0]
            dtype = leaf.dtype
            if paged and promote_store and dtype == jnp.bfloat16:
                dtype = jnp.float32
            if paged:
                shape = (n, spec.num_blocks, spec.block_size,
                         math.prod(leaf.shape[3:]))
            else:
                shape = (n, spec.num_slots, *leaf.shape[2:])
            return jnp.zeros(shape, dtype)

        self.pool = jax.tree.map(make_pool, template, self.paged)

    # ------------------------------------------------------------ gather
    def gather(self, pool: Any, tables: jax.Array) -> Any:
        """Materialize the dense decode cache for all slots.

        ``tables`` [num_slots, max_blocks] int32 — padding entries must point
        at the null block.  Paged leaves become ``[n, S, max_len, *feat]``;
        slot-state leaves pass through (they already carry the slot axis).
        """
        S, M = tables.shape
        bs = self.spec.block_size

        def leaf(p, d, paged):
            if not paged:
                return p
            n = p.shape[0]
            g = jnp.take(p, tables.reshape(-1), axis=1)       # [n, S*M, bs, F]
            return g.reshape(n, S, M * bs, *d.shape[3:])

        return jax.tree.map(leaf, pool, self.dense, self.paged)

    # ------------------------------------------------- scatter (decode)
    def scatter_decode(
        self, pool: Any, dense: Any, tables: jax.Array, pos: jax.Array
    ) -> Any:
        """Write back the one block each slot touched at ``pos`` (per-slot
        write position of this decode step); slot-state leaves are replaced
        wholesale since the dense tree *is* their storage."""
        S = tables.shape[0]
        bs = self.spec.block_size
        tb = pos // bs                                         # [S]
        phys = tables[jnp.arange(S), tb]                       # [S]

        def leaf(p, d, paged):
            if not paged:
                return d

            def pick(d_s, start):                              # d_s [n, L, f]
                return jax.lax.dynamic_slice_in_dim(d_s, start, bs, axis=1)

            blocks = jax.vmap(pick, in_axes=(1, 0), out_axes=1)(d, tb * bs)
            return p.at[:, phys].set(                          # [n, S, bs, F]
                blocks.reshape(*blocks.shape[:3], -1))

        return jax.tree.map(leaf, pool, dense, self.paged)

    # ------------------------------------------- slot migration (export)
    def export_slot(self, pool: Any, phys: jax.Array, slot: jax.Array) -> Any:
        """Pull one slot's cache state out of the pool as a self-contained
        bundle — the disaggregation hand-off unit.  Paged leaves become
        ``[n, n_blk, bs, *feat]`` (the slot's blocks in table order, in the
        dense cache's feature axes);
        slot-state leaves become ``[n, *feat]`` (the slot's row).  ``phys``
        may be padded with null-block entries: the padding rows carry
        whatever the null block holds and are ignored on import.
        """

        def leaf(p, d, paged):
            if paged:
                b = jnp.take(p, phys, axis=1)                  # [n, n_blk, bs, F]
                return b.reshape(*b.shape[:3], *d.shape[3:])
            return p[:, slot]

        return jax.tree.map(leaf, pool, self.dense, self.paged)

    # ------------------------------------------- slot migration (import)
    def import_slot(
        self, pool: Any, bundle: Any, phys: jax.Array, slot: jax.Array
    ) -> Any:
        """Deposit an :meth:`export_slot` bundle into this pool at ``phys``
        blocks + slot-state row ``slot``.  Padding entries of ``phys`` must
        point at the null block, where the extra writes land harmlessly
        (same convention as the decode scatter of inactive slots)."""

        def leaf(p, b, paged):
            if paged:
                b = b.reshape(*b.shape[:3], -1)
                return p.at[:, phys].set(b.astype(p.dtype))
            return p.at[:, slot].set(b.astype(p.dtype))

        return jax.tree.map(leaf, pool, bundle, self.paged)

    # ------------------------------------------------ scatter (prefill)
    def scatter_prefill(
        self, pool: Any, filled: Any, slot: jax.Array, phys: jax.Array
    ) -> Any:
        """Deposit a freshly-prefilled B=1 dense cache (cache_len = a block
        multiple) into ``phys`` [n_blk] pool blocks + slot-state row ``slot``."""
        bs = self.spec.block_size
        n_blk = phys.shape[0]

        def leaf(p, f, paged):
            if not paged:
                return p.at[:, slot].set(f[:, 0])
            n = p.shape[0]
            r = f[:, 0].reshape(n, n_blk, bs, -1)             # [n, n_blk, bs, F]
            return p.at[:, phys].set(r)

        return jax.tree.map(leaf, pool, filled, self.paged)
