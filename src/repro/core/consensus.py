"""Consensus application: w_i <- sum_j p_ij w_j  (paper Eq. 8/10).

Implementations with identical semantics:

  * ``mix_dense``        - stacked (m, n) einsum, used by the vmap FL
                           simulator and as the oracle in tests.
  * ``mix_sparse`` /
    ``mix_delta_sparse`` - gather-and-segment-reduce over the padded
                           neighbor list (ELL layout): O(m d n) flops and
                           O(m n) transient memory instead of O(m^2 n),
                           the m >= 4096 single-host path (DESIGN.md
                           "Sparse mixing").
  * ``mix_sharded``      - shard_map over the FL mesh axis: all_gather the
                           per-device model shard along the FL axis, then a
                           local weighted reduction.  Paper-faithful "dense"
                           collective (baseline in EXPERIMENTS.md Perf).
  * ``mix_neighbors``    - beyond-paper optimization: the physical graph is
                           sparse (degree d << m), so exchange parameters
                           only along graph edges using ppermute rounds over
                           a static edge-coloring of the base graph.
                           Collective bytes drop from O(m n) to O(d n).

All treat the model as a pytree; mixing acts leaf-wise (linearity of P).
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# consensus matmuls run at full f32: at default precision a TPU rounds the
# operands to bf16, and P @ W came out 2.1e-3 off float64 (8.5e-8 at
# HIGHEST) on a v5e at m=10, n=7850; P's row sums stayed exact at both
# (chip_smoke.py's precision probe; the CPU ignores the setting)
MIX_PRECISION = jax.lax.Precision.HIGHEST


def mix_dense(p: jax.Array, w_stack) -> jax.Array:
    """w_stack: pytree whose leaves have leading device axis m."""
    def mix_leaf(x):
        flat = x.reshape(x.shape[0], -1)
        out = jnp.matmul(p.astype(flat.dtype), flat, precision=MIX_PRECISION)
        return out.reshape(x.shape)

    return jax.tree.map(mix_leaf, w_stack)


def mix_delta_dense(p: jax.Array, w_stack):
    """Delta form w_i + sum_j p_ij (w_j - w_i); identical to mix_dense for a
    doubly stochastic P but numerically friendlier near P ~= I."""
    def mix_leaf(x):
        flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
        delta = jnp.matmul(p.astype(jnp.float32), flat, precision=MIX_PRECISION) - flat
        return (flat + delta).reshape(x.shape).astype(x.dtype)

    return jax.tree.map(mix_leaf, w_stack)


# ---------------------------------------------------------------------------
# Sparse (padded neighbor-list) forms.  ``nbr_idx`` is NeighborList.idx and
# ``(p_diag, p_off)`` come from ``mixing.build_p_ell``: p_off is zero on
# padded/inactive slots, and padded slots index the row itself, so the
# gathers are in-bounds and inert.  The slot loop is a ``fori_loop`` (not
# one (m, d_max, n) gather) to keep the transient at O(m n) regardless of
# d_max -- the whole point of the layout at m >= 4096.
# ---------------------------------------------------------------------------

def _sparse_mix_flat(nbr_idx: jax.Array, p_off: jax.Array, flat: jax.Array,
                     init: jax.Array) -> jax.Array:
    """init + sum_s p_off[:, s] * flat[nbr_idx[:, s]]  (all float32)."""

    def body(s, acc):
        j = jax.lax.dynamic_slice_in_dim(nbr_idx, s, 1, axis=1)[:, 0]
        ps = jax.lax.dynamic_slice_in_dim(p_off, s, 1, axis=1)
        return acc + ps.astype(jnp.float32) * flat[j]

    return jax.lax.fori_loop(0, nbr_idx.shape[1], body, init)


def mix_sparse(nbr_idx: jax.Array, p_diag: jax.Array, p_off: jax.Array, w_stack):
    """w_i <- p_ii w_i + sum_{j in N(i)} p_ij w_j over the neighbor list."""

    def mix_leaf(x):
        flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
        init = p_diag.astype(jnp.float32)[:, None] * flat
        return _sparse_mix_flat(nbr_idx, p_off, flat, init).reshape(x.shape).astype(x.dtype)

    return jax.tree.map(mix_leaf, w_stack)


def mix_sparse_halo(nbr_loc: jax.Array, p_diag: jax.Array, p_off: jax.Array,
                    w_local, w_halo):
    """``mix_sparse`` for one shard of a partitioned fleet: the gather
    source is the concatenated ``[own rows ; halo rows]`` buffer and
    ``nbr_loc`` indexes into it.  Same ``_sparse_mix_flat`` slot loop, same
    float32 accumulation order, gathering bit-identical row values -- so the
    mixed rows equal the single-device result bit-for-bit (DESIGN.md
    "Sharded fleet engine")."""

    def mix_leaf(x, h):
        flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
        buf = jnp.concatenate(
            [flat, h.reshape(h.shape[0], -1).astype(jnp.float32)], axis=0)
        init = p_diag.astype(jnp.float32)[:, None] * flat
        return _sparse_mix_flat(nbr_loc, p_off, buf, init).reshape(
            x.shape).astype(x.dtype)

    return jax.tree.map(mix_leaf, w_local, w_halo)


def mix_delta_sparse(nbr_idx: jax.Array, p_off: jax.Array, w_stack):
    """Delta form w_i + sum_j p_ij (w_j - w_i): identical to ``mix_sparse``
    for a stochastic P (p_ii = 1 - sum_j p_ij) but numerically friendlier
    near P ~= I (each slot contributes a small difference, not two large
    terms that cancel); needs only the off-diagonal slots."""

    def mix_leaf(x):
        flat = x.reshape(x.shape[0], -1).astype(jnp.float32)

        def body(s, acc):
            j = jax.lax.dynamic_slice_in_dim(nbr_idx, s, 1, axis=1)[:, 0]
            ps = jax.lax.dynamic_slice_in_dim(p_off, s, 1, axis=1)
            return acc + ps.astype(jnp.float32) * (flat[j] - flat)

        delta = jax.lax.fori_loop(0, nbr_idx.shape[1], body, jnp.zeros_like(flat))
        return (flat + delta).reshape(x.shape).astype(x.dtype)

    return jax.tree.map(mix_leaf, w_stack)


# ---------------------------------------------------------------------------
# Distributed forms. These run *inside* shard_map over the FL axis: each
# program instance holds its own replica's (possibly model-sharded) params.
# ---------------------------------------------------------------------------

def mix_allgather(w_local, p_row: jax.Array, axis_name: str):
    """Inside shard_map: w_local is this FL device's pytree; p_row is this
    device's row of P (length m).  all_gather over the FL axis then local
    weighted sum."""

    def mix_leaf(x):
        gathered = jax.lax.all_gather(x, axis_name)  # (m, ...)
        wts = p_row.astype(jnp.float32).reshape((-1,) + (1,) * x.ndim)
        return jnp.sum(wts * gathered.astype(jnp.float32), axis=0).astype(x.dtype)

    return jax.tree.map(mix_leaf, w_local)


def mix_psum_weighted(w_local, p_col_entry: jax.Array, axis_name: str):
    """Special case: when every device applies the same weight vector (i.e.
    uniform averaging, P = (1/m) 11^T as in a full broadcast round on a
    complete graph) a reduce (psum) suffices: bytes O(n) vs all-gather O(mn).
    p_col_entry is this device's scalar column weight."""

    def mix_leaf(x):
        return jax.lax.psum(x.astype(jnp.float32) * p_col_entry, axis_name).astype(x.dtype)

    return jax.tree.map(mix_leaf, w_local)


def edge_coloring(adjacency) -> list[list[tuple[int, int]]]:
    """Misra-Gries proper edge coloring of the static base graph: returns
    rounds of vertex-disjoint edges (matchings) that partition the edge set,
    using at most maxdeg + 1 colors (Vizing's bound, which this algorithm
    *guarantees* -- a greedy first-fit can need up to 2*maxdeg - 1).  Each
    round becomes one ppermute (pairwise swap) in ``mix_neighbors``.

    Accepts the canonical ``topology.EdgeList`` (the staging-native form --
    edges and maxdeg read off directly, no O(m^2) dense scan) or a dense
    symmetric adjacency (legacy input)."""
    from repro.core.topology import EdgeList

    if isinstance(adjacency, EdgeList):
        m = adjacency.m
        edges = list(zip(adjacency.u.tolist(), adjacency.v.tolist()))
        maxdeg = int(adjacency.degrees().max()) if edges else 0
    else:
        adjacency = np.asarray(adjacency, bool)
        m = adjacency.shape[0]
        edges = [(i, j) for i in range(m) for j in range(i + 1, m) if adjacency[i, j]]
        maxdeg = int(adjacency.sum(1).max()) if edges else 0
    if not edges:
        return []
    ncolors = maxdeg + 1
    # incident[x][c] = the neighbor reached from x over the c-colored edge
    incident: list[dict[int, int]] = [{} for _ in range(m)]
    color: dict[frozenset, int] = {}

    def free(x: int) -> int:
        return next(c for c in range(ncolors) if c not in incident[x])

    def assign(a: int, b: int, c: int) -> None:
        e = frozenset((a, b))
        old = color.get(e)
        if old is not None:
            del incident[a][old], incident[b][old]
        color[e] = c
        incident[a][c] = b
        incident[b][c] = a

    def unassign(a: int, b: int) -> None:
        old = color.pop(frozenset((a, b)))
        del incident[a][old], incident[b][old]

    for (u, v) in edges:
        # maximal fan of u starting at v: each next edge (u, f) is colored
        # with a color free on the previous fan vertex
        fan = [v]
        in_fan = {v}
        grew = True
        while grew:
            grew = False
            for c, w in incident[u].items():
                if w not in in_fan and c not in incident[fan[-1]]:
                    fan.append(w)
                    in_fan.add(w)
                    grew = True
                    break
        c = free(u)
        d = free(fan[-1])
        if c != d:
            # invert the maximal cd-path starting at u (first edge colored d)
            path, x, want = [], u, d
            while want in incident[x]:
                y = incident[x][want]
                path.append((x, y))
                x, want = y, (c if want == d else d)
            for a, b in path:
                unassign(a, b)
            for i, (a, b) in enumerate(path):
                assign(a, b, c if i % 2 == 0 else d)
        # shortest fan prefix [v .. w] that is still a fan with d free on w
        w_end = next(i for i, f in enumerate(fan) if d not in incident[f]
                     and all(color[frozenset((u, fan[j + 1]))] not in incident[fan[j]]
                             for j in range(i)))
        # rotate: shift each fan edge's color back one vertex, color (u,w)=d
        # (snapshot + unassign first: in-place shifting would momentarily
        # give two edges at u the same color and corrupt ``incident``)
        shifted = [color[frozenset((u, fan[i + 1]))] for i in range(w_end)]
        for i in range(w_end):
            unassign(u, fan[i + 1])
        for i in range(w_end):
            assign(u, fan[i], shifted[i])
        assign(u, fan[w_end], d)

    rounds: list[list[tuple[int, int]]] = [[] for _ in range(ncolors)]
    for e, c in color.items():
        a, b = sorted(e)
        rounds[c].append((a, b))
    return [r for r in rounds if r]


def mix_neighbors(
    w_local,
    p_local: jax.Array,  # (m,) this device's row of P
    axis_name: str,
    rounds: Sequence[Sequence[tuple[int, int]]],
):
    """Neighbor-only mixing via ppermute matchings (beyond-paper collective
    schedule).  For each matching round, devices swap their model with their
    matched partner and accumulate p_ij * w_j.  Devices without a partner in
    a round send to themselves (identity permutation entry).

    Equivalent to mix_allgather when P's support is inside the base graph.
    """
    idx = jax.lax.axis_index(axis_name)

    def accum(x):
        acc = x.astype(jnp.float32) * p_local[idx]
        for matching in rounds:
            # permutation: swap endpoints of each edge; others fixed
            m = p_local.shape[0]
            perm_np = list(range(m))
            for (a, b) in matching:
                perm_np[a], perm_np[b] = b, a
            pairs = [(s, perm_np[s]) for s in range(m)]
            recv = jax.lax.ppermute(x, axis_name, pairs)
            # weight of the partner we received from; unmatched devices
            # receive their own tensor back and must not re-add it
            partner = jnp.asarray(perm_np)[idx]
            wgt = jnp.where(partner != idx, p_local[partner], 0.0)
            acc = acc + wgt * recv.astype(jnp.float32)
        return acc.astype(x.dtype)

    return jax.tree.map(accum, w_local)
