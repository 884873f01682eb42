"""jit'd wrappers: padding + lane reduction + threshold compare.

Inputs are the canonical (m, D) flat rows ``efhc.flatten_stack`` builds
from the ModelSpec pytree -- D is ``ModelSpec.flat_dim``, so a real
multi-layer model just means wider rows spanning more column blocks; the
kernels are architecture-blind (DESIGN.md "Model plumbing")."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import aligned_block, cover, pad_rows_cols, row_block
from repro.kernels.trigger.kernel import trigger_sq_pallas


def trigger_sq(w: jax.Array, w_hat: jax.Array, *, block_n: int = 1024,
               interpret: bool = False) -> jax.Array:
    """(m, n) x2 -> (m,) squared deviation; pads m and n up to block
    multiples (zero pad -> no effect on the sums)."""
    m, n = w.shape
    block_m = row_block(m)
    block_n = aligned_block(n, block_n)
    rows, cols = cover(m, block_m), cover(n, block_n)
    part = trigger_sq_pallas(pad_rows_cols(w, rows, cols),
                             pad_rows_cols(w_hat, rows, cols),
                             block_m=block_m, block_n=block_n,
                             interpret=interpret)
    return part[:m].sum(axis=1)


def trigger_sq_tree(w_tree, h_tree, *, interpret: bool = False) -> jax.Array:
    """Pytree form: leaves (m, ...) are flattened and accumulated."""
    tot = None
    for w, h in zip(jax.tree.leaves(w_tree), jax.tree.leaves(h_tree)):
        m = w.shape[0]
        s = trigger_sq(w.reshape(m, -1), h.reshape(m, -1), interpret=interpret)
        tot = s if tot is None else tot + s
    return tot


def events(w, w_hat, *, n_model: int, r: float, rho: jax.Array,
           gamma_k: jax.Array, interpret: bool = False) -> jax.Array:
    dev = jnp.sqrt(trigger_sq(w, w_hat, interpret=interpret) / n_model)
    # strict inequality: Eq. 7 fires only when the deviation *exceeds* the
    # threshold, matching triggers.policy_branches (dev == threshold, e.g.
    # a zero threshold with w == w_hat, must NOT fire)
    return dev > r * rho * gamma_k
