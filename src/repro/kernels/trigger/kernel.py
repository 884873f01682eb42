"""Fused trigger-deviation Pallas kernel (paper Eq. 3 LHS).

Computes per-FL-device squared parameter deviation

    sq[i] = sum_n (w[i, n] - w_hat[i, n])^2

without materializing (w - w_hat) in HBM.  Grid (m // bm, n // bn): row
blocks are independent ("parallel"); column blocks stream (bm x bn) tiles
of w and w_hat through VMEM and fold them into a (bm x 128) f32 output
block that every column step of the row revisits ("arbitrary": the column
steps of one row block run in order, so the read-modify-write on the
revisited block is well-defined).  Lane reduction to (m,) happens in the
ops wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _trigger_kernel(w_ref, h_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    d = w_ref[...].astype(jnp.float32) - h_ref[...].astype(jnp.float32)
    sq = d * d  # (bm, bn)
    bm, bn = sq.shape
    o_ref[...] += sq.reshape(bm, bn // LANES, LANES).sum(axis=1)  # (bm, LANES)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def trigger_sq_pallas(w: jax.Array, w_hat: jax.Array, *, block_m: int = 256,
                      block_n: int = 1024, interpret: bool = False) -> jax.Array:
    """w, w_hat (m, n); m % block_m == 0 and n % block_n == 0 (the ops
    wrapper pads); returns (m, 128) partial sums."""
    m, n = w.shape
    assert m % block_m == 0 and n % block_n == 0 and block_n % LANES == 0
    return pl.pallas_call(
        _trigger_kernel,
        grid=(m // block_m, n // block_n),
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_m, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(w, w_hat)
