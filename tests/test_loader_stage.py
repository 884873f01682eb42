"""``FederatedBatches.stage`` against the per-device ``rng.choice`` loop.

``stage`` draws each iteration's minibatch indices for all devices in one
``rng.integers`` call; the loop below is the definition it must reproduce: the
same (T, m, batch) indices, and the generator left in the same state,
buffered 32-bit half included, so that a later ``stage`` or ``next``
continues the same stream.
"""
import numpy as np
import pytest

from repro.data import loader
from repro.data.loader import FederatedBatches, stage_stats

SIZES = (1, 2, 3, 4, 7, 600, 9999)


def loop_stage(parts, batch, rng, T):
    """The oracle: one ``rng.choice`` per device and iteration."""
    idx = np.empty((T, len(parts), batch), np.int32)
    for t in range(T):
        for i, p in enumerate(parts):
            idx[t, i] = rng.choice(p, size=batch, replace=True)
    return idx


def make_parts(sizes, seed=0):
    """Disjoint, shuffled parts of a dataset, of the given sizes."""
    perm = np.random.default_rng(seed).permutation(int(sum(sizes)))
    return np.split(perm, np.cumsum(sizes)[:-1])


def assert_same_as_loop(parts, batch, seed, T, *, before=lambda rng: None):
    fb = FederatedBatches(None, None, parts, batch, seed=seed)
    ref = np.random.default_rng(seed)
    before(fb.rng)
    before(ref)
    got = fb.stage(T)
    want = loop_stage(parts, batch, ref, T)
    assert got.dtype == np.int32 and got.shape == (T, len(parts), batch)
    np.testing.assert_array_equal(got, want)
    assert fb.rng.bit_generator.state == ref.bit_generator.state
    return fb, ref


FLEETS = [(s,) for s in SIZES] + [SIZES, (1, 1, 4, 1), (600,) * 10, (9999, 7, 2, 3)]


@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("batch", [1, 5, 16, 20])
@pytest.mark.parametrize("sizes", FLEETS, ids=lambda s: "-".join(map(str, s)))
def test_stage_matches_the_loop(sizes, batch, T):
    assert_same_as_loop(make_parts(sizes), batch, 1234 + 7 * batch + T, T)


@pytest.mark.parametrize("seed", [0, 3, 2**31 + 11, 2**40 + 5])
def test_stage_matches_the_loop_from_a_buffered_half(seed):
    """A generator holding a buffered half (one 32-bit draw made) and
    random fleets: the buffered half is the first one staged."""
    r = np.random.default_rng(seed)
    for _ in range(10):
        sizes = tuple(r.choice(SIZES, size=r.integers(1, 8)))
        assert_same_as_loop(make_parts(sizes), int(r.integers(1, 21)),
                            int(r.integers(0, 2**40)), int(r.integers(1, 6)),
                            before=lambda rng: rng.integers(0, 5))


@pytest.mark.parametrize("seed", [0, 1, 2, 5])
def test_rejected_draws_are_redrawn_as_numpy_does(seed):
    """A part of 2**20 + 1 samples: Lemire rejects about one half in 4096,
    so some of the 4800 draws are redrawn, exactly as the loop does."""
    before = stage_stats()
    assert_same_as_loop([np.arange(2**20 + 1)], 16, seed, 300)
    after = stage_stats()
    assert after.calls == before.calls + 1
    assert after.draws == before.draws + 300 * 16
    assert after.redraws > before.redraws


@pytest.mark.parametrize("chunk", [1, loader._CHUNK_DRAWS])
def test_rejections_across_iterations(chunk, monkeypatch):
    """Batch 7 over parts of odd sizes: rejected halves fall at every place
    in an iteration, its last draw included, and the next iteration (at
    ``chunk`` = 1, the next ``rng.integers`` call) starts after the
    redraw."""
    monkeypatch.setattr(loader, "_CHUNK_DRAWS", chunk)
    before = stage_stats().redraws
    for seed in (0, 1, 2, 5):
        assert_same_as_loop(make_parts((2**20 + 1, 3, 1, 600)), 7, seed, 300)
    assert stage_stats().redraws > before


@pytest.mark.parametrize("sizes", [(4,) * 64, (2**20,), (1, 2, 8, 1024)])
def test_power_of_two_parts_never_redraw(sizes):
    before = stage_stats().redraws
    assert_same_as_loop(make_parts(sizes), 16, 9, 50)
    assert stage_stats().redraws == before


def lemire_rejections(seed, n, draws):
    """Halves numpy's Lemire step rejects in ``draws`` draws below n from a
    fresh PCG64, counted one half at a time in its raw stream."""
    halves = np.random.PCG64(seed).random_raw(2 * draws).astype("<u8").view("<u4")
    thr = (2**32 - n) % n
    rejected, i = 0, 0
    for _ in range(draws):
        while int(halves[i]) * n % 2**32 < thr:
            rejected, i = rejected + 1, i + 1
        i += 1
    return rejected


@pytest.mark.parametrize("seed", [0, 7])
def test_redraws_count_the_halves_lemire_rejects(seed):
    n = 2**20 + 1
    before = stage_stats().redraws
    assert_same_as_loop([np.arange(n)], 16, seed, 300)
    assert stage_stats().redraws - before == lemire_rejections(seed, n, 300 * 16)


@pytest.mark.parametrize("buffered", [False, True])
@pytest.mark.parametrize("k", [0, 1, 2, 3, 1001, 2**20 + 1])
def test_halves_taken_counts_what_the_generator_handed_out(k, buffered):
    """Full-range uint32 draws take exactly one half each."""
    rng = np.random.default_rng(k + 11)
    if buffered:
        rng.integers(0, 2**32, dtype=np.uint32)
    before = rng.bit_generator.state
    rng.integers(0, 2**32, size=k, dtype=np.uint32)
    assert loader._halves_taken(before, rng.bit_generator.state) == k


def test_two_stages_in_a_row_continue_the_stream():
    parts = make_parts((3, 600, 1, 7))
    fb, ref = assert_same_as_loop(parts, 3, 21, 5)
    np.testing.assert_array_equal(fb.stage(4), loop_stage(parts, 3, ref, 4))
    assert fb.rng.bit_generator.state == ref.bit_generator.state


@pytest.mark.parametrize("batch", [1, 3])
def test_next_stage_next_interleave(batch):
    """Odd numbers of halves per step, so that each of next(), stage() and
    next() starts from, or leaves, a buffered half."""
    sizes = (5, 1, 600)
    parts = make_parts(sizes)
    x = np.arange(sum(sizes), dtype=np.float32)[:, None] * np.ones((1, 2), np.float32)
    y = np.arange(sum(sizes))
    fb = FederatedBatches(x, y, parts, batch, seed=77)
    ref = np.random.default_rng(77)
    for T in (3, 0, 2):
        xb, yb = fb.next()
        want = loop_stage(parts, batch, ref, 1)[0]
        np.testing.assert_array_equal(yb, y[want])
        np.testing.assert_array_equal(xb, x[want])
        np.testing.assert_array_equal(fb.stage(T), loop_stage(parts, batch, ref, T))
        assert fb.rng.bit_generator.state == ref.bit_generator.state


def test_empty_part_raises_like_the_loop():
    parts = [np.arange(3), np.arange(0)]
    with pytest.raises(ValueError):
        loop_stage(parts, 2, np.random.default_rng(0), 1)
    with pytest.raises(ValueError, match="empty"):
        FederatedBatches(None, None, parts, 2, seed=0).stage(1)
    # nothing drawn, nothing to raise about
    assert FederatedBatches(None, None, parts, 2, seed=0).stage(0).shape == (0, 2, 2)


def test_stage_stats_snapshot_is_a_copy():
    s = stage_stats()
    FederatedBatches(None, None, make_parts((3, 4)), 2, seed=0).stage(2)
    assert stage_stats().calls == s.calls + 1 and stage_stats().draws == s.draws + 8
    assert stage_stats().redraws == s.redraws
