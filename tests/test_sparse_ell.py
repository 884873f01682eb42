"""Sparse (padded neighbor-list / ELL) mixing subsystem units plus the
Misra-Gries edge-coloring invariants.

Kept separate from test_mixing_consensus.py / test_kernels.py on purpose:
those modules importorskip hypothesis, and this coverage must run even in
environments without it (the pinned container).  Full-trajectory parity of
``mix_impl="sparse*"`` against the dense engine lives in
tests/test_scan_parity.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import consensus, mixing, triggers
from repro.core.topology import make_process, neighbor_list, scatter_ell
from repro.kernels.mixing.ops import mix_sparse as mix_sparse_kernel
from repro.kernels.mixing.ops import mix_sparse_tree
from repro.kernels.mixing.ref import mix_ref, mix_sparse_ref


def _ell_graph_comm(m, seed, topology="rgg"):
    """Dense and ELL views of the same (graph, comm) realization."""
    g = make_process(m, topology, seed=seed)
    nl = neighbor_list(g.base)
    adj = jnp.asarray(g.base)
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.random(m) < 0.6)
    comm = triggers.communication_matrix(v, adj)
    idx, mask = jnp.asarray(nl.idx), jnp.asarray(nl.mask)
    rows = jnp.arange(m)[:, None]
    comm_ell = jnp.logical_and(comm[rows, idx], mask)
    return adj, comm, idx, mask, comm_ell


# ------------------------------------------------------ ELL P construction --

@pytest.mark.parametrize("m,seed", [(6, 0), (12, 3), (33, 7)])
def test_build_p_ell_matches_dense(m, seed):
    """The ELL transition pieces scatter back to exactly Eq. 9's dense P
    (so it inherits double stochasticity and symmetry)."""
    adj, comm, idx, mask, comm_ell = _ell_graph_comm(m, seed)
    p = mixing.build_p(adj, comm)
    pd, po = mixing.build_p_ell(idx, mask, comm_ell)
    p_from_ell = scatter_ell(idx, po) + jnp.diag(pd)
    np.testing.assert_allclose(np.asarray(p_from_ell), np.asarray(p), atol=1e-6)
    mixing.assert_doubly_stochastic(p_from_ell)


@pytest.mark.parametrize("m,seed", [(20, 1), (64, 4)])
def test_assert_doubly_stochastic_ell_matches_dense_check(m, seed):
    """The O(m d) ELL invariant check accepts exactly what the dense check
    accepts -- and catches a broken P without ever scattering to (m, m)."""
    adj, comm, idx, mask, comm_ell = _ell_graph_comm(m, seed)
    pd, po = mixing.build_p_ell(idx, mask, comm_ell)
    mixing.assert_doubly_stochastic_ell(idx, pd, po)
    # symmetry violation: bump one active slot's weight
    po_bad = np.asarray(po).copy()
    i, s = np.argwhere(np.asarray(comm_ell))[0]
    po_bad[i, s] += 0.01
    with pytest.raises(AssertionError):
        mixing.assert_doubly_stochastic_ell(idx, 1.0 - po_bad.sum(-1), po_bad)
    # row-sum violation
    with pytest.raises(AssertionError):
        mixing.assert_doubly_stochastic_ell(idx, np.asarray(pd) + 0.1, po)


def test_assert_doubly_stochastic_ell_at_m4096():
    """The large-fleet form exists precisely for shapes where the dense
    scatter is the (m, m) matrix the sparse engine never builds."""
    from repro.core.topology import fleet_radius

    m = 4096
    g = make_process(m, "rgg", radius=fleet_radius(m), seed=0)
    nl = g.neighbors()
    idx, mask = jnp.asarray(nl.idx), jnp.asarray(nl.mask)
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.random(m) < 0.5)
    comm_ell = jnp.logical_and(jnp.logical_or(v[:, None], v[idx]), mask)
    pd, po = mixing.build_p_ell(idx, mask, comm_ell)
    mixing.assert_doubly_stochastic_ell(idx, pd, po)


# ------------------------------------------------------- consensus mixes ----

def test_mix_sparse_matches_dense():
    m, n = 14, 9
    adj, comm, idx, mask, comm_ell = _ell_graph_comm(m, 5)
    p = mixing.build_p(adj, comm)
    pd, po = mixing.build_p_ell(idx, mask, comm_ell)
    w = {"x": jax.random.normal(jax.random.PRNGKey(4), (m, n)),
         "y": jax.random.normal(jax.random.PRNGKey(5), (m, 2, 3))}
    dense = consensus.mix_dense(p, w)
    sparse = consensus.mix_sparse(idx, pd, po, w)
    delta = consensus.mix_delta_sparse(idx, po, w)
    for k in w:
        np.testing.assert_allclose(np.asarray(sparse[k]), np.asarray(dense[k]),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(delta[k]), np.asarray(dense[k]),
                                   atol=1e-5)


def test_mix_sparse_preserves_mean():
    """Doubly-stochastic P: the device-mean must be invariant under the
    neighbor-list mix exactly as under the dense mix."""
    m, n = 16, 6
    adj, comm, idx, mask, comm_ell = _ell_graph_comm(m, 11)
    pd, po = mixing.build_p_ell(idx, mask, comm_ell)
    w = {"a": jax.random.normal(jax.random.PRNGKey(0), (m, n))}
    mixed = consensus.mix_sparse(idx, pd, po, w)
    np.testing.assert_allclose(np.asarray(mixed["a"].mean(0)),
                               np.asarray(w["a"].mean(0)), atol=1e-5)


# ------------------------------------------------------- pallas kernel ------

def _ell_p(m: int, seed: int):
    """Random active-slot ELL transition pieces on an RGG neighbor list."""
    g = make_process(m, "rgg", seed=seed)
    nl = neighbor_list(g.base)
    rng = np.random.default_rng(seed)
    active = jnp.asarray(nl.mask & (rng.random(nl.mask.shape) < 0.7))
    po = jnp.where(active, 0.5 / nl.d_max, 0.0).astype(jnp.float32)
    pd = 1.0 - po.sum(-1)
    return jnp.asarray(nl.idx), pd, po


# m=300 and m=600: padded rows, several row blocks, slots padded to whole
# SMEM tiles
@pytest.mark.parametrize("m,n", [(8, 512), (16, 1000), (33, 257), (64, 64),
                                 (300, 300), (600, 257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mix_sparse_kernel_sweep(m, n, dtype):
    idx, pd, po = _ell_p(m, seed=m)
    w = jax.random.normal(jax.random.PRNGKey(m + n), (m, n)).astype(dtype)
    got = mix_sparse_kernel(idx, pd, po, w, interpret=True)
    want = mix_sparse_ref(idx, pd, po, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_mix_sparse_kernel_equals_dense_scatter():
    """The ELL kernel is the dense P @ W with P scattered from the slots."""
    m, n = 16, 300
    idx, pd, po = _ell_p(m, seed=3)
    w = jax.random.normal(jax.random.PRNGKey(5), (m, n))
    p = scatter_ell(idx, po) + jnp.diag(pd)
    np.testing.assert_allclose(
        np.asarray(mix_sparse_kernel(idx, pd, po, w, interpret=True)),
        np.asarray(mix_ref(p, w)), atol=1e-5)


def test_mix_sparse_tree_matches_leafwise():
    idx, pd, po = _ell_p(8, seed=1)
    key = jax.random.PRNGKey(0)
    tree = {"a": jax.random.normal(key, (8, 3, 5)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (8, 17))}
    got = mix_sparse_tree(idx, pd, po, tree, interpret=True)
    for k in tree:
        flat = tree[k].reshape(8, -1)
        np.testing.assert_allclose(
            np.asarray(got[k].reshape(8, -1)),
            np.asarray(mix_sparse_ref(idx, pd, po, flat)), atol=1e-5)


# ------------------------------------------------------- edge coloring ------

@pytest.mark.parametrize("topology", ["rgg", "er", "ring"])
@pytest.mark.parametrize("m,seed", [(10, 5), (16, 0), (33, 2), (64, 1)])
def test_edge_coloring_is_proper_covers_and_vizing(topology, m, seed):
    """Misra-Gries invariants on every supported topology: each round is a
    matching (vertex-disjoint), the rounds partition the base edge set, and
    the round count respects Vizing's maxdeg + 1 (a greedy first-fit does
    NOT guarantee this -- it needs up to 2 maxdeg - 1)."""
    g = make_process(m, topology, seed=seed)
    adj = np.asarray(g.base)
    rounds = consensus.edge_coloring(adj)
    seen = []
    for matching in rounds:
        nodes = [u for e in matching for u in e]
        assert len(nodes) == len(set(nodes)), "matching must be vertex-disjoint"
        seen.extend(frozenset(e) for e in matching)
    expect = {frozenset((i, j)) for i in range(m) for j in range(i + 1, m)
              if adj[i, j]}
    assert len(seen) == len(set(seen)), "each edge colored exactly once"
    assert set(seen) == expect, "every base edge must be covered"
    assert len(rounds) <= int(adj.sum(1).max()) + 1, "Vizing bound"


def test_edge_coloring_empty_graph():
    assert consensus.edge_coloring(np.zeros((5, 5), bool)) == []


def test_edge_coloring_accepts_edge_list():
    """The staging-native input: coloring an EdgeList must produce the same
    rounds as coloring its dense scatter (edges iterate in the same
    canonical order either way)."""
    g = make_process(24, "rgg", seed=9)
    assert consensus.edge_coloring(g.edges) == consensus.edge_coloring(g.base)
