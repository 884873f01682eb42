"""Deterministic batch iterators.

* ``FederatedBatches``: per-device minibatch sampling for the FL simulator -
  produces stacked (m, batch, ...) arrays so the simulator can vmap over the
  device axis.  Sampling is uniform with replacement (matches the paper's
  S_i^(k) "chosen uniformly at random from the local dataset").
  ``stage(T)`` pre-draws T iterations worth of sample *indices* at once so
  the scan engine can keep the whole horizon on device (gathering rows from
  the device-resident dataset per step) instead of round-tripping a fresh
  host batch every iteration.  It takes one ``rng.integers(0, n, (t, m,
  batch))`` call per t iterations with each device's part size n as the
  bound: numpy draws that array in C order by the same Lemire step on the
  32-bit halves of the stream that ``rng.choice(part, batch)`` takes (and
  nothing for n = 1), so the indices, and the generator's state after
  them, are bit-exact to a per-device ``rng.choice`` loop.  ``stage_stats()`` counts
  the calls, draws and redraws.
* ``lm_batches``: contiguous next-token LM batches from a token stream.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Draws per ``rng.integers`` call in ``stage``: a call covers max(1,
# _CHUNK_DRAWS // (m * batch)) iterations, so its temporaries (12 bytes a
# draw) hold 2**18 draws or one iteration's, whichever is more.
_CHUNK_DRAWS = 1 << 18
# PCG64's LCG multiplier (numpy's ``PCG_DEFAULT_MULTIPLIER_128``).
_PCG64_MULT = 0x2360ED051FC65DA44385DF649FCCF645
_MASK128 = (1 << 128) - 1


@dataclasses.dataclass
class StageStats:
    """Lifetime counters of ``FederatedBatches.stage``: ``calls``, index
    ``draws``, and ``redraws`` -- halves numpy's Lemire step rejected and
    drew again (about n / 2**32 of a part of n samples' draws, none for n a
    power of two)."""

    calls: int = 0
    draws: int = 0
    redraws: int = 0


_STAGE_STATS = StageStats()


def stage_stats() -> StageStats:
    """Snapshot of the staging counters since the process started."""
    return dataclasses.replace(_STAGE_STATS)


class FederatedBatches:
    def __init__(self, x: np.ndarray, y: np.ndarray, parts: list[np.ndarray], batch: int, seed: int = 0):
        self.x, self.y = x, y
        self.parts = parts
        self.batch = batch
        self.rng = np.random.default_rng(seed)

    @property
    def m(self) -> int:
        return len(self.parts)

    def next(self) -> tuple[np.ndarray, np.ndarray]:
        """Returns (xb (m, batch, dim), yb (m, batch))."""
        xs, ys = [], []
        for p in self.parts:
            idx = self.rng.choice(p, size=self.batch, replace=True)
            xs.append(self.x[idx])
            ys.append(self.y[idx])
        return np.stack(xs), np.stack(ys)

    def stage(self, T: int) -> np.ndarray:
        """Pre-draws the dataset indices for T iterations: (T, m, batch) int32.

        Returns what T ``next()`` calls would draw (same per-step,
        per-device draw order) and leaves the rng where they would, buffered
        32-bit half included, so a scan over staged indices reproduces the
        legacy per-step loop sample-for-sample and a later ``stage`` or
        ``next`` continues the same stream.  One ``rng.integers`` call
        draws a chunk of iterations for all devices (module docstring), so
        the host holds one chunk's draws beside the output.  Indices are
        returned instead of gathered rows to keep staging O(T m batch) ints
        rather than O(T m batch dim) floats; the engine gathers from the
        device-resident (x, y) arrays inside the scanned step.
        """
        m, batch = len(self.parts), self.batch
        n = np.array([len(p) for p in self.parts], np.int64)
        idx = np.empty((T, m, batch), np.int32)
        _STAGE_STATS.calls += 1
        if T == 0 or batch == 0:
            return idx
        if (n == 0).any():
            raise ValueError("a cannot be empty unless no samples are taken")
        flat = np.concatenate(self.parts).astype(np.int32)
        start = (np.cumsum(n) - n)[:, None]
        before = self.rng.bit_generator.state
        step = max(1, _CHUNK_DRAWS // (m * batch))
        for t0 in range(0, T, step):
            rows = self.rng.integers(0, n[:, None], size=(min(step, T - t0), m, batch))
            rows += start
            idx[t0 : t0 + len(rows)] = flat[rows]
        draws = T * int((n > 1).sum()) * batch
        _STAGE_STATS.draws += draws
        _STAGE_STATS.redraws += _halves_taken(before, self.rng.bit_generator.state) - draws
        return idx


def _halves_taken(before: dict, after: dict) -> int:
    """The 32-bit halves a PCG64 handed out between two of its states: twice
    the LCG steps from one to the other, found bit by bit as pcg's
    ``distance`` does, plus the half buffered before, less the one buffered
    after.  ``default_rng`` always builds a PCG64."""
    cur, new = before["state"]["state"], after["state"]["state"]
    mult, plus = _PCG64_MULT, before["state"]["inc"]
    steps = 0
    for bit in range(128):
        if cur == new:
            break
        if (cur ^ new) >> bit & 1:
            cur = (cur * mult + plus) & _MASK128
            steps |= 1 << bit
        plus = (mult + 1) * plus & _MASK128
        mult = mult * mult & _MASK128
    return 2 * steps + before["has_uint32"] - after["has_uint32"]


def lm_batches(stream: np.ndarray, batch: int, seq: int, *, seed: int = 0):
    """Yields dicts {tokens, targets} of shape (batch, seq)."""
    rng = np.random.default_rng(seed)
    n = len(stream) - seq - 1
    while True:
        starts = rng.integers(0, n, size=batch)
        toks = np.stack([stream[s : s + seq] for s in starts])
        tgts = np.stack([stream[s + 1 : s + seq + 1] for s in starts])
        yield {"tokens": toks.astype(np.int32), "targets": tgts.astype(np.int32)}


def federated_lm_parts(stream: np.ndarray, m: int) -> list[np.ndarray]:
    """Contiguous shard of the stream per FL device (non-iid by position)."""
    return np.array_split(stream, m)
