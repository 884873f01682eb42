"""Plain reference of EF-HC (arXiv:2211.12640, Alg. 1) for the benchmark.

Written from the paper's equations, independent of the program under
test: it imports nothing of it and takes nothing it made.  What it
shares with the program is what the scenario fixes: the seed, the data
and fabric the benchmark generated, and the documented random streams
(model init, bandwidths, edge dropout, gossip draws and minibatch indices
are pure functions of the seed, so both sides realize the same ones).

One iteration k over m devices, each holding w_i and the last broadcast
w_hat_i (Sec. II):

  Event 1  G^(k): every fabric edge is kept with probability 1 - drop,
           drawn per edge from (process seed, k); links that appear since
           k-1 exchange unconditionally.
  Event 2  v_i = ||w_i - w_hat_i|| / sqrt(D) > r * rho_i * gamma_k, with
           rho_i = 1/b_i (efhc) or 1/b_M (global); zero fires always,
           gossip with probability 1/m.
  Event 3  p_ij = min(1/(1+d_i), 1/(1+d_j)) on the links used, p_ii the
           complement; w_i <- sum_j p_ij w_j.  w_hat_i <- w_i (pre-mix) for
           the devices that fired.
  Event 4  one SGD step on the device's minibatch at alpha_k = alpha0 /
           sqrt(1 + k) (gamma_k = alpha_k).

The step is judged against a trajectory the program produced: the
reference follows the program's broadcast decisions (``forced_v``) so that
one trigger that rounding tipped does not part the two trajectories, and
charges each decision that disagrees with its own by how far its own
deviation lay from the threshold.  Everything runs in float32 with
matmuls and convolutions at full precision; ``dtype=bfloat16`` gives the
control, the same reference computed a precision lower.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EVAL_BLOCK = 512
POLICIES = ("efhc", "zero", "global", "gossip")


@dataclasses.dataclass(frozen=True)
class Scenario:
    """Everything fixed across the answers of one cell."""

    model: str  # svm | cnn
    dim: int
    n_classes: int
    m: int
    batch: int
    T: int
    eval_every: int
    r: float
    b_mean: float
    sigma_n: float
    alpha0: float
    drop: float
    process_seed: int
    nbr: np.ndarray  # (m, d_max) int32 neighbour table, padding = self
    mask: np.ndarray  # (m, d_max) bool real slots
    # the operands of the model's matmuls and convolutions: "bfloat16"
    # (rounded to bf16, products accumulated in f32: the TPU's default
    # precision, which the configurations state) or "float32" (full
    # precision: the CPU's default, and the chip under "highest")
    matmul_operands: str
    cnn: tuple[int, int, int] = (8, 16, 32)  # conv1, conv2, hidden widths

    def __post_init__(self):
        if self.m > 46340:
            raise ValueError(f"m={self.m}: Event 1's edge id lo * m + hi "
                             "must fit int32 (m <= 46340)")
        if self.matmul_operands not in ("bfloat16", "float32"):
            raise ValueError(f"matmul_operands {self.matmul_operands!r}")


# ---------------------------------------------------------------- models --

def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(sc: Scenario, key, dtype):
    """Per-device parameters, leaves (m, ...).  svm: one N(0, 0.01^2) draw
    per device from split(key, m); cnn: one He-initialized draw shared by
    every device (split(key, 4) over the four weight tensors)."""
    m, C = sc.m, sc.n_classes
    if sc.model == "svm":
        keys = jax.random.split(key, m)
        w = jax.vmap(lambda k: jax.random.normal(k, (sc.dim, C)) * 0.01)(keys)
        return {"w": w.astype(dtype), "b": jnp.zeros((m, C), dtype)}
    c1, c2, hid = sc.cnn
    side = math.isqrt(sc.dim)
    feat = (side // 4) * (side // 4) * c2
    k1, k2, k3, k4 = jax.random.split(key, 4)
    one = {"c1": _normal(k1, (3, 3, 1, c1), np.sqrt(2.0 / 9), dtype),
           "cb1": jnp.zeros((c1,), dtype),
           "c2": _normal(k2, (3, 3, c1, c2), np.sqrt(2.0 / (9 * c1)), dtype),
           "cb2": jnp.zeros((c2,), dtype),
           "w3": _normal(k3, (feat, hid), np.sqrt(2.0 / feat), dtype),
           "b3": jnp.zeros((hid,), dtype),
           "w4": _normal(k4, (hid, C), np.sqrt(2.0 / hid), dtype),
           "b4": jnp.zeros((C,), dtype)}
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (m,) + a.shape), one)


def _pool(h):
    """2x2 average pool, stride 2 (even sides only)."""
    n, s1, s2, c = h.shape
    return h.reshape(n, s1 // 2, 2, s2 // 2, 2, c).mean(axis=(2, 4))


@jax.custom_vjp
def _mm_bf16(a, b):
    """a @ b with both operands rounded to bf16 and the products summed in
    f32, forward and backward alike."""
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _mm_bf16_fwd(a, b):
    return _mm_bf16(a, b), (a, b)


def _mm_bf16_bwd(res, g):
    a, b = res
    return (_mm_bf16(g, jnp.swapaxes(b, -1, -2)),
            _mm_bf16(jnp.swapaxes(a, -1, -2), g))


_mm_bf16.defvjp(_mm_bf16_fwd, _mm_bf16_bwd)


def _mm(sc: Scenario, a, b):
    if a.dtype == jnp.float32 and sc.matmul_operands == "bfloat16":
        return _mm_bf16(a, b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _conv3x3(sc: Scenario, h, k):
    """3x3 'SAME' convolution, stride 1, as one matmul over the nine
    shifted copies of the zero-padded input (NHWC input, HWIO kernel)."""
    n, s1, s2, c = h.shape
    p = jnp.pad(h, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols = jnp.concatenate([p[:, i:i + s1, j:j + s2, :]
                            for i in range(3) for j in range(3)], axis=-1)
    return _mm(sc, cols.reshape(-1, 9 * c), k.reshape(9 * c, -1)).reshape(
        n, s1, s2, -1)


def logits(sc: Scenario, w, x):
    """One device's logits for rows x (n, dim)."""
    x = x.astype(jax.tree.leaves(w)[0].dtype)
    if sc.model == "svm":
        return _mm(sc, x, w["w"]) + w["b"]
    side = math.isqrt(sc.dim)
    h = x.reshape(x.shape[0], side, side, 1)
    for k, b in (("c1", "cb1"), ("c2", "cb2")):
        h = _pool(jax.nn.relu(_conv3x3(sc, h, w[k]) + w[b]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(_mm(sc, h, w["w3"]) + w["b3"])
    return _mm(sc, h, w["w4"]) + w["b4"]


def loss(sc: Scenario, w, x, y):
    z = logits(sc, w, x)
    if sc.model == "svm":
        # multi-class hinge: mean over rows of sum_{j != y} max(0, 1 - z_y + z_j) / C
        zy = jnp.take_along_axis(z, y[:, None], axis=1)
        viol = jnp.maximum(0.0, 1.0 - zy + z)
        viol = jnp.where(jax.nn.one_hot(y, sc.n_classes, dtype=bool), 0.0, viol)
        return viol.sum(-1).mean() / sc.n_classes
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()


# ------------------------------------------------------------------ step --

def _edge_keep(sc: Scenario, k, lo, hi):
    """Event 1's per-edge draw: uniform keyed by (process seed, k, edge)."""
    key = jax.random.fold_in(jax.random.PRNGKey(sc.process_seed),
                             jnp.asarray(k, jnp.uint32))
    ids = (lo * sc.m + hi).reshape(-1)
    u = jax.vmap(lambda e: jax.random.uniform(jax.random.fold_in(key, e)))(ids)
    return u.reshape(lo.shape) >= sc.drop


def _graph(sc: Scenario, k):
    nbr = jnp.asarray(sc.nbr)
    rows = jnp.arange(sc.m, dtype=nbr.dtype)[:, None]
    keep = _edge_keep(sc, k, jnp.minimum(rows, nbr), jnp.maximum(rows, nbr))
    return jnp.logical_and(jnp.asarray(sc.mask), keep)


def _sqdist(a, b):
    return sum(jnp.sum(jnp.square((x - y).astype(jnp.float32)).reshape(x.shape[0], -1), axis=1)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _step(sc: Scenario, state, x, y, forced_v, policy, use_forced: bool):
    """One iteration; returns the new state and the iteration's record."""
    w, w_hat, prev_adj, key, k, bw = state
    m = sc.m
    key, k_trig, _ = jax.random.split(key, 3)
    kf = k.astype(jnp.float32)
    alpha = sc.alpha0 / (1.0 + kf / 1.0) ** 0.5
    D = sum(int(np.prod(l.shape[1:])) for l in jax.tree.leaves(w))
    dtype = jax.tree.leaves(w)[0].dtype
    nbr = jnp.asarray(sc.nbr)

    adj = _graph(sc, k)
    deg = adj.sum(1, dtype=jnp.int32)

    # Event 2, the reference's own decision and its distance to the threshold
    dev = jnp.sqrt(_sqdist(w, w_hat) / D)
    rho = jnp.where(policy == 0, 1.0 / bw, 1.0 / sc.b_mean)
    thr = sc.r * rho * alpha
    u = jax.random.uniform(k_trig, (m,))
    p_g = 1.0 / m
    own = jnp.select([policy == 1, policy == 3], [jnp.ones((m,), bool), u < p_g],
                     dev > thr)
    margin = jnp.select([policy == 1, policy == 3],
                        [jnp.ones((m,)), jnp.abs(u - p_g) / (u + p_g)],
                        jnp.abs(dev - thr) / jnp.maximum(dev + thr, 1e-30))
    v = forced_v if use_forced else own
    charged = jnp.where(v != own, margin, 0.0)

    # Events 1 and 3
    new_links = jnp.logical_and(adj, ~prev_adj)
    comm = jnp.logical_or(jnp.logical_and(jnp.logical_or(v[:, None], v[nbr]), adj),
                          new_links)
    inv = 1.0 / (1.0 + deg.astype(jnp.float32))
    weights = jnp.minimum(inv[:, None], inv[nbr]) * comm
    p_off = weights.astype(dtype)
    p_diag = (1.0 - weights.sum(1)).astype(dtype)

    def mix(leaf):
        flat = leaf.reshape(m, -1)

        def slot(s, acc):
            return acc + p_off[:, s, None] * flat[nbr[:, s]]

        out = jax.lax.fori_loop(0, nbr.shape[1], slot, p_diag[:, None] * flat)
        return out.reshape(leaf.shape)

    w_mixed = jax.tree.map(mix, w)
    w_hat = jax.tree.map(
        lambda h, c: jnp.where(v.reshape((m,) + (1,) * (c.ndim - 1)), c, h), w_hat, w)

    # Event 4
    lvals, grads = jax.vmap(jax.value_and_grad(partial(loss, sc)))(w_mixed, x, y)
    w = jax.tree.map(lambda a, g: (a - alpha.astype(dtype) * g).astype(dtype),
                     w_mixed, grads)

    degf = deg.astype(jnp.float32)
    used = comm.sum(1, dtype=jnp.int32)
    usedf = used.astype(jnp.float32)
    frac = jnp.where(degf > 0, usedf / jnp.maximum(degf, 1.0), 0.0)
    mean = jax.tree.map(lambda a: a.astype(jnp.float32).mean(0), w)
    cons = sum(jnp.sum(jnp.square(a.astype(jnp.float32) - b))
               for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(mean)))
    rec = {"loss": lvals.astype(jnp.float32), "v": own, "deg": deg, "comm_count": used,
           "tx_time": jnp.mean(frac * D / bw),
           "util": jnp.sum(usedf * D) / jnp.maximum(jnp.sum(degf * bw), 1e-12),
           "consensus_err": cons, "charged": charged}
    return (w, w_hat, adj, key, k + 1, bw), rec


def _accuracy(sc: Scenario, w, x_test, y_test):
    """Mean test accuracy over the devices, EVAL_BLOCK devices at a time
    (a fleet's logits on every test image at once would not fit)."""
    hits = jax.lax.map(lambda wi: jnp.mean(
        jnp.argmax(logits(sc, wi, x_test), -1) == y_test), w,
        batch_size=EVAL_BLOCK)
    return hits.mean()


class Reference:
    """Jitted reference programs of one scenario, built once per cell."""

    def __init__(self, sc: Scenario, x, y, x_test, y_test,
                 dtype=jnp.float32):
        self.sc, self.dtype = sc, dtype
        self.x, self.y = jnp.asarray(x), jnp.asarray(y)
        self.x_test, self.y_test = jnp.asarray(x_test), jnp.asarray(y_test)
        self._steps = {
            f: jax.jit(lambda st, x, y, ix, fv, pol, f=f: _step(
                sc, st, x[ix], y[ix], fv, pol, f))
            for f in (False, True)}
        self._acc = jax.jit(partial(_accuracy, sc))
        self._init = jax.jit(self._init_state)

    def _init_state(self, seed):
        sc = self.sc
        k_bw, k_init, k_state = jax.random.split(jax.random.PRNGKey(seed), 3)
        lo = max((1.0 - sc.sigma_n) * sc.b_mean, 1e-3 * sc.b_mean)
        bw = jax.random.uniform(k_bw, (sc.m,), minval=lo,
                                maxval=(1.0 + sc.sigma_n) * sc.b_mean
                                ).astype(self.dtype)
        w = init_params(sc, k_init, self.dtype)
        return (w, w, _graph(sc, 0), k_state, jnp.asarray(0, jnp.int32), bw)

    def run(self, seed: int, policy: str, idx: np.ndarray,
            forced_v: np.ndarray | None = None) -> dict:
        """The trajectory of one answer: ``idx`` (T, m, batch) minibatch
        indices; ``forced_v`` (T, m) the decisions to follow, or None to
        follow the reference's own."""
        sc = self.sc
        T, E = sc.T, sc.eval_every
        state = self._init(jnp.asarray(seed, jnp.int32))
        pol = jnp.asarray(POLICIES.index(policy), jnp.int32)
        step = self._steps[forced_v is not None]
        fv = (np.zeros((T, sc.m), bool) if forced_v is None
              else np.asarray(forced_v, bool))
        recs, acc = [], np.zeros(T, np.float32)
        for t in range(T):
            state, rec = step(state, self.x, self.y, jnp.asarray(idx[t]),
                              jnp.asarray(fv[t]), pol)
            recs.append(rec)
            if t % E == 0:
                acc[t:t + E] = float(self._acc(state[0], self.x_test, self.y_test))
        acc[T - 1] = float(self._acc(state[0], self.x_test, self.y_test))
        out = {k: np.stack([np.asarray(r[k]) for r in recs]) for k in recs[0]}
        out["acc"] = acc
        out["bandwidths"] = np.asarray(state[5])
        return out
