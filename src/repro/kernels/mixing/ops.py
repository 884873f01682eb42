"""jit'd public wrapper: pads m and n to the block sizes, applies the kernel
leaf-wise over a stacked parameter pytree."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import aligned_block, cover, pad_rows_cols, row_block
from repro.kernels.mixing.kernel import (SMEM_TILE, mix_pallas,
                                         mix_sparse_pallas)


def mix(p: jax.Array, w: jax.Array, *, block_n: int = 512,
        interpret: bool = False) -> jax.Array:
    """p (m, m); w (m, n) -> (m, n); zero-pads m and n up to block
    multiples (zero rows/columns of P and W add nothing)."""
    m, n = w.shape
    block_m = row_block(m)
    block_n = aligned_block(n, block_n)
    rows, cols = cover(m, block_m), cover(n, block_n)
    out = mix_pallas(pad_rows_cols(p.astype(jnp.float32), rows, rows),
                     pad_rows_cols(w, rows, cols), block_m=block_m,
                     block_n=block_n, interpret=interpret)
    return out[:m, :n]


def mix_tree(p: jax.Array, tree, *, block_n: int = 512, interpret: bool = False):
    """Apply the consensus mixing to a pytree whose leaves have a leading
    fl axis: each leaf is flattened to (m, -1), mixed, and reshaped."""
    def one(leaf):
        m = leaf.shape[0]
        flat = leaf.reshape(m, -1)
        return mix(p, flat, block_n=block_n, interpret=interpret).reshape(leaf.shape)

    return jax.tree.map(one, tree)


def mix_sparse(nbr_idx: jax.Array, p_diag: jax.Array, p_off: jax.Array,
               w: jax.Array, *, block_n: int = 256,
               interpret: bool = False) -> jax.Array:
    """ELL gather-mix: nbr_idx/p_off (m, d_max), p_diag (m,), w (m, n).
    Builds the kernel's flat slot tables (slot 0 = the row itself with
    p_ii) and zero-pads rows, slots and columns up to block multiples:
    padded slots and rows carry zero weight and gather row 0, so they are
    inert."""
    m, n = w.shape
    block_m = row_block(m)
    block_n = aligned_block(n, block_n)
    rows, cols = cover(m, block_m), cover(n, block_n)
    slots = nbr_idx.shape[1] + 1
    if block_m < rows:  # a row block's slots must fill whole SMEM tiles
        slots = cover(slots, SMEM_TILE // math.gcd(block_m, SMEM_TILE))
    self_idx = jnp.arange(m, dtype=jnp.int32)[:, None]
    idx = jnp.concatenate([self_idx, nbr_idx.astype(jnp.int32)], axis=1)
    p = jnp.concatenate([p_diag.astype(jnp.float32).reshape(m, 1),
                         p_off.astype(jnp.float32)], axis=1)
    out = mix_sparse_pallas(pad_rows_cols(idx, rows, slots).reshape(-1),
                            pad_rows_cols(p, rows, slots).reshape(-1),
                            pad_rows_cols(w, rows, cols), block_m=block_m,
                            block_n=block_n, interpret=interpret)
    return out[:m, :n]


def mix_sparse_tree(nbr_idx: jax.Array, p_diag: jax.Array, p_off: jax.Array,
                    tree, *, block_n: int = 256, interpret: bool = False):
    """Leaf-wise ``mix_sparse`` over a stacked parameter pytree."""
    def one(leaf):
        m = leaf.shape[0]
        flat = leaf.reshape(m, -1)
        return mix_sparse(nbr_idx, p_diag, p_off, flat, block_n=block_n,
                          interpret=interpret).reshape(leaf.shape)

    return jax.tree.map(one, tree)
