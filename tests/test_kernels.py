"""Per-kernel shape/dtype sweeps, interpret=True vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.mixing.ops import mix, mix_tree
from repro.kernels.mixing.ref import mix_ref
from repro.kernels.swa.ops import swa_attention
from repro.kernels.swa.ref import swa_ref
from repro.kernels.trigger.ops import events, trigger_sq, trigger_sq_tree
from repro.kernels.trigger.ref import events_ref, trigger_sq_ref


# ---------------------------------------------------------------- mixing ----

# m=300 and m=600 pad to 2 and 3 row blocks of ROW_BLOCK=256: several row
# blocks, and several contraction steps accumulating per output block
@pytest.mark.parametrize("m,n", [(4, 512), (8, 1000), (16, 4096), (3, 64), (32, 700),
                                 (300, 700), (600, 1100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mixing_sweep(m, n, dtype):
    key = jax.random.PRNGKey(m * 1000 + n)
    p = jax.nn.softmax(jax.random.normal(key, (m, m)), -1)
    w = jax.random.normal(key, (m, n)).astype(dtype)
    got = mix(p, w, interpret=True)
    want = mix_ref(p, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_mixing_tree_matches_leafwise():
    key = jax.random.PRNGKey(0)
    m = 4
    p = jax.nn.softmax(jax.random.normal(key, (m, m)), -1)
    tree = {"a": jax.random.normal(key, (m, 3, 5)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (m, 17))}
    got = mix_tree(p, tree, interpret=True)
    for k in tree:
        flat = tree[k].reshape(m, -1)
        np.testing.assert_allclose(np.asarray(got[k].reshape(m, -1)),
                                   np.asarray(mix_ref(p, flat)), atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(2, 12), n=st.integers(1, 600), seed=st.integers(0, 999))
def test_mixing_hypothesis(m, n, seed):
    key = jax.random.PRNGKey(seed)
    p = jax.nn.softmax(jax.random.normal(key, (m, m)), -1)
    w = jax.random.normal(jax.random.fold_in(key, 1), (m, n))
    np.testing.assert_allclose(np.asarray(mix(p, w, interpret=True)),
                               np.asarray(mix_ref(p, w)), atol=1e-4)


# ---------------------------------------------------------------- trigger ---

# m=300 and m=600: padded rows and several row blocks, each revisiting its
# (bm, 128) output block over the column steps
@pytest.mark.parametrize("m,n", [(4, 1024), (10, 3000), (16, 257), (2, 64),
                                 (300, 1500), (600, 2100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_trigger_sweep(m, n, dtype):
    key = jax.random.PRNGKey(m + n)
    w = jax.random.normal(key, (m, n)).astype(dtype)
    h = jax.random.normal(jax.random.fold_in(key, 1), (m, n)).astype(dtype)
    got = trigger_sq(w, h, interpret=True)
    want = trigger_sq_ref(w, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-4)


def test_trigger_events_match_ref():
    key = jax.random.PRNGKey(7)
    m, n = 8, 500
    w = jax.random.normal(key, (m, n))
    h = w + 0.01 * jax.random.normal(jax.random.fold_in(key, 1), (m, n))
    rho = jnp.linspace(0.5, 2.0, m)
    got = events(w, h, n_model=n, r=1.0, rho=rho, gamma_k=jnp.asarray(0.01),
                 interpret=True)
    want = events_ref(w, h, n_model=n, r=1.0, rho=rho, gamma_k=jnp.asarray(0.01))
    assert (np.asarray(got) == np.asarray(want)).all()


def test_trigger_tree_accumulates():
    key = jax.random.PRNGKey(9)
    m = 4
    t1 = {"a": jax.random.normal(key, (m, 100)), "b": jax.random.normal(key, (m, 7, 3))}
    t2 = jax.tree.map(lambda x: x + 0.5, t1)
    got = trigger_sq_tree(t1, t2, interpret=True)
    want = sum(trigger_sq_ref(a.reshape(m, -1), b.reshape(m, -1))
               for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


# ---------------------------------------------------------------- swa -------

@pytest.mark.parametrize("shape", [
    # (B, S, H, G, dh, window, bq, bk)
    (1, 256, 4, 2, 64, 64, 64, 32),
    (2, 128, 2, 2, 32, 128, 32, 32),
    (1, 512, 4, 1, 64, 128, 128, 64),
    (1, 128, 8, 4, 128, 32, 32, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_sweep(shape, dtype):
    b, s, h, g, dh, win, bq, bk = shape
    key = jax.random.PRNGKey(sum(shape))
    q = jax.random.normal(key, (b, s, h, dh)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, g, dh)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, g, dh)).astype(dtype)
    got = swa_attention(q, k, v, window=win, block_q=bq, block_k=bk, interpret=True)
    want = swa_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                   v.transpose(0, 2, 1, 3), window=win).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_swa_never_attends_outside_window():
    b, s, h, g, dh, win = 1, 128, 2, 2, 32, 32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, g, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, g, dh))
    v2 = v.at[:, 0].add(100.0)  # perturb token 0's value
    y1 = swa_attention(q, k, v, window=win, block_q=32, block_k=32, interpret=True)
    y2 = swa_attention(q, k, v2, window=win, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y1[:, win:]), np.asarray(y2[:, win:]),
                               atol=1e-5)
    assert np.abs(np.asarray(y1[:, 0]) - np.asarray(y2[:, 0])).max() > 1.0
