"""Pallas TPU kernels (validated with interpret=True on CPU):
  mixing/  - fused consensus mixing P @ W        (paper Event 3)
  trigger/ - fused ||w - w_hat||^2 reduction      (paper Event 2)
  swa/     - sliding-window causal flash attention (long_500k path)
"""
import jax.numpy as jnp

LANES = 128  # TPU lane width: last-dim tiles must be multiples of this
ROW_BLOCK = 256  # row tile of the fleet kernels (m axis); a multiple of 8


def aligned_block(n: int, block_n: int) -> int:
    """Streaming block size for a length-n minor axis: the configured block,
    shrunk to the 128-lane-aligned cover of n so narrow inputs (small model
    leaves) pad to lane alignment rather than a full default block."""
    return min(block_n, max(LANES, -(-n // LANES) * LANES))


def row_block(m: int) -> int:
    """Row-axis block for an m-row operand: the whole axis when it fits one
    block (a full-extent block needs no 8-row alignment, so the paper's
    m=10 runs unpadded), else ``ROW_BLOCK``, with the caller padding m up
    to a multiple of it."""
    return min(m, ROW_BLOCK)


def cover(n: int, block: int) -> int:
    """The smallest multiple of ``block`` that is >= n."""
    return -(-n // block) * block


def pad_rows_cols(x, rows: int, cols: int):
    """Zero-pads a 2-D array up to (rows, cols); a no-op when it fits."""
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    return jnp.pad(x, ((0, pr), (0, pc))) if pr or pc else x
