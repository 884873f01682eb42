"""The plain reference's building blocks against direct computations."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen
from bench.reference import efhc as ref


def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32), np.float64)


def test_bf16_operand_matmul_and_its_gradients():
    rng = np.random.default_rng(0)
    a = rng.normal(size=(5, 7)).astype(np.float32)
    b = rng.normal(size=(7, 3)).astype(np.float32)
    g = rng.normal(size=(5, 3)).astype(np.float32)
    np.testing.assert_allclose(ref._mm_bf16(a, b), _bf16(a) @ _bf16(b), rtol=1e-6)
    _, vjp = jax.vjp(ref._mm_bf16, a, b)
    da, db = vjp(jnp.asarray(g))
    np.testing.assert_allclose(da, _bf16(g) @ _bf16(b).T, rtol=1e-6)
    np.testing.assert_allclose(db, _bf16(a).T @ _bf16(g), rtol=1e-6)


def test_conv_as_matmul_matches_xla_conv():
    rng = np.random.default_rng(1)
    h = rng.normal(size=(2, 6, 6, 3)).astype(np.float32)
    k = rng.normal(size=(3, 3, 3, 4)).astype(np.float32)
    sc = ref.Scenario(model="cnn", dim=36, n_classes=2, m=1, batch=1, T=1,
                      eval_every=1, r=1.0, b_mean=1.0, sigma_n=0.5, alpha0=0.1,
                      drop=0.0, process_seed=0, nbr=np.zeros((1, 1), np.int32),
                      mask=np.zeros((1, 1), bool), matmul_operands="float32")
    want = jax.lax.conv_general_dilated(
        h, k, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(ref._conv3x3(sc, h, k), want, rtol=1e-5, atol=1e-5)


def test_multi_class_hinge_by_hand():
    sc = ref.Scenario(model="svm", dim=2, n_classes=3, m=1, batch=2, T=1,
                      eval_every=1, r=1.0, b_mean=1.0, sigma_n=0.5, alpha0=0.1,
                      drop=0.0, process_seed=0, nbr=np.zeros((1, 1), np.int32),
                      mask=np.zeros((1, 1), bool), matmul_operands="float32")
    w = {"w": jnp.asarray([[1.0, 0.0, 0.5], [0.0, 1.0, 0.0]]),
         "b": jnp.zeros(3)}
    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]])
    y = jnp.asarray([0, 2])
    # row 0: z = (1, 0, .5), y=0: max(0, 1-1+0) + max(0, 1-1+.5) = .5
    # row 1: z = (0, 2, 0),  y=2: max(0, 1-0+0) + max(0, 1-0+2) = 4
    assert float(ref.loss(sc, w, x, y)) == pytest.approx((0.5 + 4.0) / 2 / 3)


def test_neighbour_table_lists_every_edge_both_ways():
    u, v = gen.rgg_edges(200, 0.15, seed=3)
    nbr, mask = gen.neighbours(u, v, 200)
    got = {(i, int(j)) for i in range(200) for j, ok in zip(nbr[i], mask[i]) if ok}
    assert got == {(int(a), int(b)) for a, b in zip(u, v)} | {
        (int(b), int(a)) for a, b in zip(u, v)}
    assert (nbr[~mask] == np.nonzero(~mask)[0]).all()  # padding points home


def test_rgg_matches_the_papers_fabric_builder():
    """The benchmark's copy builds the same fabric as the program's builder
    for the same seed (the service builds its own from the spec)."""
    from repro.core.topology import random_geometric_graph

    for m, r, seed in ((10, 0.4, 0), (10, 0.4, 5), (500, 0.08, 1)):
        edges, _ = random_geometric_graph(m, r, seed)
        u, v = gen.rgg_edges(m, r, seed)
        np.testing.assert_array_equal(u, edges.u)
        np.testing.assert_array_equal(v, edges.v)


def test_scenario_refuses_edge_ids_past_int32():
    kw = dict(model="svm", dim=2, n_classes=3, batch=1, T=1, eval_every=1,
              r=1.0, b_mean=1.0, sigma_n=0.5, alpha0=0.1, drop=0.0,
              process_seed=0, nbr=np.zeros((1, 1), np.int32),
              mask=np.zeros((1, 1), bool), matmul_operands="float32")
    ref.Scenario(m=46340, **kw)
    with pytest.raises(ValueError, match="int32"):
        ref.Scenario(m=46341, **kw)
    with pytest.raises(ValueError, match="matmul_operands"):
        ref.Scenario(m=1, **{**kw, "matmul_operands": "int8"})


@pytest.mark.parametrize("case", range(6))
def test_staging_at_once_draws_what_the_loop_draws(case):
    rs = np.random.default_rng(case)
    m, batch, T = int(rs.integers(1, 40)), int(rs.integers(1, 20)), int(rs.integers(1, 5))
    sizes = rs.choice([1, 2, 3, 4, 7, 600, 9999], size=m)
    if case == 5:  # Lemire rejects about 1 draw in 4000 below 2**20 + 1
        sizes[0], batch, T = 2**20 + 1, 16, 300
    parts = [np.sort(rs.choice(2**21, size=int(s), replace=False)) for s in sizes]
    seed = int(rs.integers(0, 2**31))
    np.testing.assert_array_equal(gen.stage(parts, batch, seed, T),
                                  gen.stage_loop(parts, batch, seed, T))
    if case == 5:
        assert gen._stage_at_once(parts, batch, seed, T) is None


def test_staging_matches_the_programs_sampler():
    from repro.data.loader import FederatedBatches

    x = np.zeros((300, 2), np.float32)
    y = np.arange(300) % 10
    parts = gen.by_labels(y, 12, 3, seed=4)
    np.testing.assert_array_equal(FederatedBatches(x, y, parts, 16, seed=99).stage(7),
                                  gen.stage(parts, 16, 99, 7))
