"""Fused consensus-mixing Pallas kernels (paper Eq. 8/10).

``mix_pallas`` - dense OUT = P @ W: the doubly-stochastic transition matrix
P (m x m) into the stacked flat parameter matrix W (m x n).  Grid
(m // bm, n // bn, m // bk): each (bm x bn) output block accumulates
P[bm, bk] @ W[bk, bn] over the contraction blocks, so neither P nor W is
ever held whole in VMEM and m is bounded by HBM, not by VMEM.

``mix_sparse_pallas`` - the m >= 4096 path: P in padded neighbor-list (ELL)
layout, a gather + slot-loop reduce costing O(m d_max) per element column
instead of O(m^2) (DESIGN.md "Sparse mixing").

Both kernels are HBM-bound (arithmetic intensity ~m or ~d_max flops per
byte); the point of fusing is to keep every intermediate out of HBM.
Matmuls run at ``Precision.HIGHEST``: the simulator's contract is f32
consensus, and a default-precision TPU matmul rounds P and W to bf16
(``consensus.MIX_PRECISION`` gives the measured error).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mix_kernel(p_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(p_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "interpret"))
def mix_pallas(p: jax.Array, w: jax.Array, *, block_m: int = 256,
               block_n: int = 512, interpret: bool = False) -> jax.Array:
    """p (m, m) float32; w (m, n).  Returns (m, n) in w.dtype.
    m must be a multiple of block_m (which tiles both P axes) and n of
    block_n; the ops wrapper pads."""
    m, n = w.shape
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)
    bm = bk = block_m
    return pl.pallas_call(
        _mix_kernel,
        grid=(m // bm, n // block_n, m // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        scratch_shapes=[pltpu.VMEM((bm, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(p, w)


def _mix_sparse_kernel(idx_ref, p_ref, w_ref, o_ref, *, slots: int):
    """Gather-mix for one (bm x bn) output block.  ``w_ref`` is the whole
    (m x bn) column block of W, resident in VMEM across the row blocks.
    The ids and weights of the block's rows sit in SMEM as flat
    (bm * slots,) vectors whose slot 0 is the row itself with weight p_ii,
    so every term is one dynamic-offset (1 x bn) row load.  The slot order
    and f32 accumulation order are those of ``consensus.mix_sparse``."""
    bm = o_ref.shape[0]

    def term(e):
        return p_ref[e] * w_ref[pl.ds(idx_ref[e], 1), :].astype(jnp.float32)

    def row(r, carry):
        e0 = r * slots
        acc = jax.lax.fori_loop(1, slots, lambda s, a: a + term(e0 + s),
                                term(e0))
        o_ref[pl.ds(r, 1), :] = acc.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bm, row, 0)


# default scoped VMEM on a v5e TensorCore; the sparse kernel raises its
# limit only when the resident W column block needs more
_DEFAULT_SCOPED_VMEM = 16 * 2**20
# largest resident W column block the sparse kernel plans for: v5e has
# 128 MiB of VMEM per core, and the rest holds the output blocks
SPARSE_W_BLOCK_BYTES = 96 * 2**20
# XLA tiles a 1-D SMEM operand in 1024-element chunks; a flat slot block
# that is not a whole number of them fails Mosaic's layout check
SMEM_TILE = 1024


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "interpret"))
def mix_sparse_pallas(idx: jax.Array, p: jax.Array, w: jax.Array, *,
                      block_m: int = 256, block_n: int = 256,
                      interpret: bool = False) -> jax.Array:
    """ELL consensus mixing: out_i = sum_s p[i, s] * w[idx[i, s]].

    idx (m * slots,) int32 and p (m * slots,) float32 are the flat slot
    tables: slot 0 of each row is the row itself with p_ii, the rest are
    its neighbors with zero weight on padded / inactive slots; w (m, n).
    m must be a multiple of block_m and n of block_n, and a row block's
    slots a whole number of SMEM tiles unless it spans all m rows (the ops
    wrapper pads).  Grid (n // bn, m // bm): the row axis is innermost, so
    each (m x bn) column block of W is fetched once and kept
    single-buffered while every row block gathers from it."""
    m, n = w.shape
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)
    slots = idx.shape[0] // m
    flat_block = block_m * slots
    assert block_m == m or flat_block % SMEM_TILE == 0, (block_m, slots)
    w_block = m * block_n * w.dtype.itemsize
    if w_block > SPARSE_W_BLOCK_BYTES:
        raise ValueError(
            f"sparse_pallas holds an (m, {block_n}) column block of W in "
            f"VMEM: {w_block / 2**20:.0f} MiB at m={m} exceeds the "
            f"{SPARSE_W_BLOCK_BYTES / 2**20:.0f} MiB plan")
    need = w_block + 2 * block_m * block_n * w.dtype.itemsize + 2**20
    smem = pltpu.SMEM
    return pl.pallas_call(
        functools.partial(_mix_sparse_kernel, slots=slots),
        grid=(n // block_n, m // block_m),
        in_specs=[
            pl.BlockSpec((flat_block,), lambda j, i: (i,), memory_space=smem),
            pl.BlockSpec((flat_block,), lambda j, i: (i,), memory_space=smem),
            pl.BlockSpec((m, block_n), lambda j, i: (0, j),
                         pipeline_mode=pl.Buffered(1)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), w.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the resident column block exceeds the default scoped limit
            # from m ~ 16384 on at block_n=256 (16 MiB per 16384 rows)
            vmem_limit_bytes=(need if need > _DEFAULT_SCOPED_VMEM else None)),
        interpret=interpret,
    )(idx, p, w)
