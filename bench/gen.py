"""Traffic and data generators of the benchmark, seeded by the caller.

Copies of the program's sound generators, kept here so that a change to
the program cannot move the yardstick:

* ``image_dataset``  FMNIST-shaped class-conditional blobs (28x28, C
  classes), optionally box-blurred for conv models;
* ``by_labels``      the paper's non-iid split, L labels per device;
* ``rgg_edges``      a connected random geometric graph on the unit square,
  the radius grown by 1.15 until it connects (the paper's fabric, and the
  massive-IoT fleet's at ``fleet_radius``);
* ``fleet_radius``   the radius ladder: 0.4 for small fleets, 0.15 at
  mid-scale, then a mean degree of about 24 (a fixed radio range);
* ``stage``          per-device minibatch indices, uniform with replacement
  from the device's local data, iteration-major, device-minor.

All of them are plain numpy and deterministic in their seeds.
"""
from __future__ import annotations

import math

import numpy as np


def image_dataset(n: int, *, n_classes: int, dim: int, noise: float,
                  seed: int, proto_seed: int, smooth: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """(x (n, dim) float32 in [0, 1], y (n,) int32): class prototypes from
    ``proto_seed``, plus Gaussian noise drawn from ``seed``; ``smooth``
    box-blurs the prototypes over the square image (window 2*smooth+1)."""
    rng = np.random.default_rng(seed)
    protos = np.random.default_rng(proto_seed).normal(
        0.5, 0.35, size=(n_classes, dim)).astype(np.float32)
    if smooth:
        side = math.isqrt(dim)
        if side * side != dim:
            raise ValueError(f"smooth needs a square dim, got {dim}")
        p = protos.reshape(n_classes, side, side).astype(np.float64)
        k = np.ones(2 * smooth + 1) / (2 * smooth + 1)
        for ax in (1, 2):
            p = np.apply_along_axis(lambda v: np.convolve(v, k, "same"), ax, p)
        p = 0.5 + (p - p.mean()) * (0.35 / p.std())
        protos = p.reshape(n_classes, dim).astype(np.float32)
    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    x = protos[y] + rng.normal(0.0, noise, size=(n, dim)).astype(np.float32)
    return np.clip(x, 0.0, 1.0).astype(np.float32), y


def by_labels(y: np.ndarray, m: int, labels_per_device: int, *,
              seed: int) -> list[np.ndarray]:
    """Device i holds labels [i*L, i*L+L) mod C; each class's samples,
    permuted, are dealt round-robin over its holders.  Returns m sorted
    int64 index arrays."""
    rng = np.random.default_rng(seed)
    classes = np.unique(y)
    n_classes, L = len(classes), labels_per_device
    by_class = [rng.permutation(np.nonzero(y == c)[0]) for c in classes]
    class_of_slot = (np.arange(m)[:, None] * L + np.arange(L)[None, :]) % n_classes
    slot_dev = np.repeat(np.arange(m), L)
    dev_parts, idx_parts = [], []
    for ci in range(n_classes):
        holders = slot_dev[class_of_slot.ravel() == ci]
        if holders.size and by_class[ci].size:
            dev_parts.append(holders[np.arange(by_class[ci].size) % holders.size])
            idx_parts.append(by_class[ci])
    dev = np.concatenate(dev_parts)
    idx = np.concatenate(idx_parts).astype(np.int64)
    order = np.lexsort((idx, dev))
    bounds = np.cumsum(np.bincount(dev, minlength=m))[:-1]
    return np.split(idx[order], bounds)


def fleet_radius(m: int) -> float:
    if m <= 64:
        return 0.4
    if m <= 256:
        return 0.15
    return float(np.sqrt(24.0 / (np.pi * m)))


def _pairs_within(pts: np.ndarray, r: float) -> tuple[np.ndarray, np.ndarray]:
    """All pairs i < j with squared distance <= r^2, by a cell list of side
    >= r (candidates from the 3x3 neighbourhood of each point's cell)."""
    m = pts.shape[0]
    ncell = max(1, min(int(np.floor(1.0 / r)), int(np.sqrt(m)) + 1))
    cx = (pts[:, 0] * ncell).astype(np.int64)
    cy = (pts[:, 1] * ncell).astype(np.int64)
    cell = cx * ncell + cy
    order = np.argsort(cell, kind="stable")
    starts = np.searchsorted(cell[order], np.arange(ncell * ncell + 1))
    ii_all, jj_all = [], []
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            tx, ty = cx + dx, cy + dy
            ok = (tx >= 0) & (tx < ncell) & (ty >= 0) & (ty < ncell)
            tcell = np.where(ok, tx * ncell + ty, 0)
            n = np.where(ok, starts[tcell + 1] - starts[tcell], 0)
            ii = np.repeat(np.arange(m), n)
            off = np.arange(ii.size) - np.repeat(np.cumsum(n) - n, n)
            jj = order[np.repeat(np.where(ok, starts[tcell], 0), n) + off]
            keep = ii < jj
            ii_all.append(ii[keep])
            jj_all.append(jj[keep])
    ii, jj = np.concatenate(ii_all), np.concatenate(jj_all)
    d2 = ((pts[ii] - pts[jj]) ** 2).sum(-1)
    sel = d2 <= r * r
    return ii[sel], jj[sel]


def _connected(u: np.ndarray, v: np.ndarray, m: int) -> bool:
    label = np.arange(m)
    while True:
        prev = label.copy()
        lo = np.minimum(label[u], label[v])
        np.minimum.at(label, u, lo)
        np.minimum.at(label, v, lo)
        while True:
            nxt = label[label]
            if np.array_equal(nxt, label):
                break
            label = nxt
        if np.array_equal(label, prev):
            return bool((label == 0).all())


def rgg_edges(m: int, radius: float, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Canonical edge list (u < v, lexsorted, int32) of a connected random
    geometric graph: m uniform points, the radius grown by 1.15 until the
    graph connects."""
    pts = np.random.default_rng(seed).uniform(size=(m, 2))
    r = radius
    for _ in range(64):
        ii, jj = _pairs_within(pts, r)
        if m <= 1 or (ii.size and _connected(ii, jj, m)):
            order = np.lexsort((jj, ii))
            return ii[order].astype(np.int32), jj[order].astype(np.int32)
        r *= 1.15
    raise RuntimeError("no connected random geometric graph")


def neighbours(u: np.ndarray, v: np.ndarray, m: int) -> tuple[np.ndarray, np.ndarray]:
    """Padded neighbour table of an undirected edge list: (m, d_max) int32
    indices (padding points at the row itself) and the (m, d_max) mask of
    real slots."""
    src = np.concatenate([u, v]).astype(np.int64)
    dst = np.concatenate([v, u]).astype(np.int64)
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    deg = np.bincount(src, minlength=m)
    d_max = max(1, int(deg.max()) if deg.size else 1)
    idx = np.tile(np.arange(m, dtype=np.int32)[:, None], (1, d_max))
    mask = np.zeros((m, d_max), bool)
    slot = np.arange(src.size) - np.repeat(np.cumsum(deg) - deg, deg)
    idx[src, slot] = dst
    mask[src, slot] = True
    return idx, mask


def stage(parts: list[np.ndarray], batch: int, seed: int, T: int) -> np.ndarray:
    """(T, m, batch) int32 sample indices: at every iteration each device
    draws ``batch`` of its own samples uniformly with replacement, devices
    in order, from one ``default_rng(seed)`` stream: ``rng.choice(part,
    batch)`` per device and iteration (``stage_loop``), computed at once."""
    fast = _stage_at_once(parts, batch, seed, T)
    return stage_loop(parts, batch, seed, T) if fast is None else fast


def stage_loop(parts: list[np.ndarray], batch: int, seed: int, T: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    idx = np.empty((T, len(parts), batch), np.int32)
    for t in range(T):
        for i, p in enumerate(parts):
            idx[t, i] = rng.choice(p, size=batch, replace=True)
    return idx


def _stage_at_once(parts, batch, seed, T) -> np.ndarray | None:
    """``stage_loop``'s draws from the raw stream: numpy draws an index
    below n from the next 32-bit half of the generator's 64-bit outputs
    (low half first) by Lemire's method, (half * n) >> 32, and draws
    nothing for n = 1.  None where Lemire would have rejected a draw (a
    chance of about n / 2**32 each)."""
    n = np.tile(np.array([len(p) for p in parts], np.uint64), T)
    live = n > 1
    need = int(live.sum()) * batch
    raw = np.random.default_rng(seed).bit_generator.random_raw((need + 1) // 2)
    halves = np.stack([raw & 0xFFFFFFFF, raw >> 32], -1).reshape(-1)[:need]
    prod = np.zeros((n.size, batch), np.uint64)
    prod[live] = halves.reshape(-1, batch) * n[live, None]
    threshold = ((2**32 - n) % np.maximum(n, 1))[:, None]
    if ((prod & 0xFFFFFFFF) < threshold)[live].any():
        return None
    offsets = np.concatenate([[0], np.cumsum([len(p) for p in parts])[:-1]])
    rows = np.tile(offsets, T)[:, None] + (prod >> 32).astype(np.int64)
    return np.concatenate(parts)[rows].reshape(T, len(parts), batch).astype(np.int32)
