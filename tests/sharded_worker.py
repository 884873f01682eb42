"""Subprocess worker for the multi-device sharded-engine acceptance tests.

XLA_FLAGS=--xla_force_host_platform_device_count=8 must be set before any
jax import, so the in-process test suite (whose jax is already initialized
with however many devices it got) launches this script in a fresh
interpreter.  Modes:

    python tests/sharded_worker.py golden   # m=8, 8 shards vs golden artifact
    python tests/sharded_worker.py parity   # m=256, 8 shards vs single device
    python tests/sharded_worker.py fabrics  # scale-free/clustered + dynamics
    python tests/sharded_worker.py faults   # fault stack + watchdog parity
    python tests/sharded_worker.py vmap     # vmap(engine) == solo cells, S=2

Prints "SHARDED-WORKER-OK" on success; any assertion failure exits nonzero
with a traceback.  Invoked by tests/test_golden_trajectory.py and
tests/test_scan_parity.py; runnable by hand for debugging.
"""
import dataclasses
import json
import os
import pathlib
import sys

assert "jax" not in sys.modules, "worker must set XLA_FLAGS before jax"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

from repro.core.topology import make_process  # noqa: E402
from repro.data.loader import FederatedBatches  # noqa: E402
from repro.data.partition import by_labels  # noqa: E402
from repro.data.synthetic import image_dataset  # noqa: E402
from repro.fl.simulator import SimConfig, run  # noqa: E402

GOLDEN = pathlib.Path(__file__).parent / "golden" / "efhc_m8_trajectory.json"


def check_golden():
    """The m=8 golden trajectory, reproduced by the sharded engine at 8
    shards (ms=1: every neighbor is a halo row -- the maximal-exchange
    corner).  Same fields and tolerances as the single-device golden test:
    integer channels exact, floats to fp32 tolerance."""
    import jax

    assert jax.device_count() >= 8, jax.device_count()
    M, T, DIM = 8, 18, 24
    x, y = image_dataset(600, seed=0, dim=DIM)
    parts = by_labels(y, M, 3)
    graph = make_process(M, "rgg", time_varying="edge_dropout", drop=0.3,
                         seed=0)
    sim = SimConfig(m=M, iters=T, dim=DIM, batch=8, r=50.0, seed=0,
                    trace="summary", mix_impl="sharded", shards=8)
    batches = FederatedBatches(x, y, parts, sim.batch, seed=2)
    res = run(sim, graph, batches, None, eval_every=5, engine="scan")

    want = json.loads(GOLDEN.read_text())
    assert (want["m"], want["iters"], want["dim"]) == (M, T, DIM)
    np.testing.assert_allclose(res.bandwidths, np.asarray(want["bandwidths"]),
                               rtol=1e-5)
    for f in ("v", "comm_count", "deg"):
        got = np.asarray(getattr(res, f), np.int64)
        assert np.array_equal(got, np.asarray(want[f], np.int64)), \
            f"sharded engine shifted the golden realization on {f}"
    for f in ("loss", "tx_time", "util", "consensus_err"):
        np.testing.assert_allclose(
            np.asarray(getattr(res, f), np.float64), np.asarray(want[f]),
            rtol=2e-4, atol=2e-5, err_msg=f"sharded golden diverged on {f}")


def check_parity():
    """Acceptance: at m=256 the sharded engine (8 shards) is bit-exact with
    the single-device sparse engine on every channel except the
    hierarchical consensus_err, across all three time-varying fabrics."""
    import jax

    assert jax.device_count() >= 8, jax.device_count()
    m, T, dim = 256, 4, 32
    x, y = image_dataset(1024, seed=0, dim=dim)
    rng = np.random.default_rng(0)
    parts = [np.sort(p) for p in np.array_split(rng.permutation(len(y)), m)]
    sim = SimConfig(m=m, iters=T, dim=dim, r=50.0, seed=0, trace="summary")
    mk = lambda: FederatedBatches(x, y, parts, sim.batch, seed=2)

    kw = {"edge_dropout": dict(drop=0.3), "partition_cycle": dict(cycle_len=2)}
    for kind in ("static", "edge_dropout", "partition_cycle"):
        graph = make_process(m, "rgg", radius=0.15, time_varying=kind, seed=0,
                             **kw.get(kind, {}))
        ref = run(dataclasses.replace(sim, mix_impl="sparse"), graph, mk(),
                  None, eval_every=T)
        sh = run(dataclasses.replace(sim, mix_impl="sharded", shards=8),
                 graph, mk(), None, eval_every=T)
        for f in ("v", "comm_count", "deg", "loss", "tx_time", "util",
                  "bandwidths"):
            assert (np.asarray(getattr(sh, f))
                    == np.asarray(getattr(ref, f))).all(), \
                f"{kind}: sharded != single-device on {f}"
        np.testing.assert_allclose(sh.consensus_err, ref.consensus_err,
                                   rtol=1e-5, err_msg=kind)


def check_fabrics():
    """ISSUE 9 acceptance: the scale-free and clustered fabrics run dense vs
    sparse vs sharded (8 shards) at m=256 with bit-equal discrete channels,
    and the sharded engine realizes the IDENTICAL resource stream as the
    single-device engine under full dynamics (churn + stragglers + budget +
    bandwidth walk) -- positional draws sliced by owned rows."""
    import jax

    assert jax.device_count() >= 8, jax.device_count()
    m, T, dim = 256, 4, 32
    x, y = image_dataset(1024, seed=0, dim=dim)
    rng = np.random.default_rng(0)
    parts = [np.sort(p) for p in np.array_split(rng.permutation(len(y)), m)]
    sim = SimConfig(m=m, iters=T, dim=dim, r=50.0, seed=0, trace="summary")
    mk = lambda: FederatedBatches(x, y, parts, sim.batch, seed=2)

    for topology in ("scale_free", "clustered"):
        graph = make_process(m, topology, time_varying="edge_dropout",
                             drop=0.3, seed=0)
        dense = run(sim, graph, mk(), None, eval_every=T)
        sparse = run(dataclasses.replace(sim, mix_impl="sparse"), graph,
                     mk(), None, eval_every=T)
        sh = run(dataclasses.replace(sim, mix_impl="sharded", shards=8),
                 graph, mk(), None, eval_every=T)
        for f in ("v", "comm_count", "deg"):
            a = np.asarray(getattr(dense, f))
            assert (a == np.asarray(getattr(sparse, f))).all(), \
                f"{topology}: sparse != dense on {f}"
            assert (a == np.asarray(getattr(sh, f))).all(), \
                f"{topology}: sharded != dense on {f}"
        for f in ("loss", "tx_time", "util", "bandwidths"):
            np.testing.assert_allclose(
                np.asarray(getattr(sparse, f)), np.asarray(getattr(dense, f)),
                atol=1e-4, err_msg=f"{topology}: sparse vs dense {f}")
            np.testing.assert_allclose(
                np.asarray(getattr(sh, f)), np.asarray(getattr(sparse, f)),
                atol=1e-4, err_msg=f"{topology}: sharded vs sparse {f}")
        np.testing.assert_allclose(sh.consensus_err, sparse.consensus_err,
                                   rtol=1e-5, err_msg=topology)

    # full dynamics on a clustered fabric: discrete channels (including the
    # resource counts) bit-equal across shard counts; util re-associates fp
    n_bytes = 4 * (dim * 10 + 10)
    dyn = dataclasses.replace(sim, policy="zero", churn_rate=0.2,
                              straggle_rate=0.2, bw_walk=0.1,
                              budget_bytes=2.5 * n_bytes)
    graph = make_process(m, "clustered", time_varying="edge_dropout",
                         drop=0.3, seed=0)
    ref = run(dataclasses.replace(dyn, mix_impl="sparse"), graph, mk(),
              None, eval_every=T)
    sh = run(dataclasses.replace(dyn, mix_impl="sharded", shards=8), graph,
             mk(), None, eval_every=T)
    assert np.asarray(ref.down_count).max() > 0, "dynamics must engage"
    for f in ("v", "comm_count", "deg", "down_count", "exhausted_count",
              "bandwidths"):
        assert (np.asarray(getattr(sh, f))
                == np.asarray(getattr(ref, f))).all(), \
            f"dynamics: sharded != single-device on {f}"
    for f in ("loss", "tx_time", "util"):
        np.testing.assert_allclose(
            np.asarray(getattr(sh, f)), np.asarray(getattr(ref, f)),
            atol=1e-4, err_msg=f"dynamics: sharded vs single-device {f}")
    np.testing.assert_allclose(sh.consensus_err, ref.consensus_err, rtol=1e-5)


def check_faults():
    """ISSUE 10 acceptance: the sharded engine (8 shards) realizes the
    IDENTICAL fault stream and watchdog verdicts as the single-device
    sparse engine under the full fault stack -- cluster outages, a
    scripted bridge partition, flapping links, crash/rejoin with warm
    start, and the B-connectivity watchdog (pmax halo propagation)."""
    import jax

    assert jax.device_count() >= 8, jax.device_count()
    m, T, dim = 256, 6, 32
    x, y = image_dataset(1024, seed=0, dim=dim)
    rng = np.random.default_rng(0)
    parts = [np.sort(p) for p in np.array_split(rng.permutation(len(y)), m)]
    sim = SimConfig(m=m, iters=T, dim=dim, r=50.0, seed=0, trace="summary",
                    policy="zero", cluster_fail_rate=0.15,
                    cluster_recover_rate=0.3, partition_start=2,
                    partition_len=2, flap_rate=0.2, flap_len=2,
                    crash_rate=0.1, rejoin_rate=0.3, warm_start=True,
                    watchdog_window=3)
    mk = lambda: FederatedBatches(x, y, parts, sim.batch, seed=2)
    graph = make_process(m, "clustered", time_varying="edge_dropout",
                         drop=0.3, seed=0)
    ref = run(dataclasses.replace(sim, mix_impl="sparse"), graph, mk(),
              None, eval_every=T)
    sh = run(dataclasses.replace(sim, mix_impl="sharded", shards=8), graph,
             mk(), None, eval_every=T)
    assert np.asarray(ref.fault_down_count).max() > 0, "faults must engage"
    for f in ("v", "comm_count", "deg", "fault_down_count", "stale_max",
              "window_connected", "window_needed", "bandwidths"):
        assert (np.asarray(getattr(sh, f))
                == np.asarray(getattr(ref, f))).all(), \
            f"faults: sharded != single-device on {f}"
    for f in ("loss", "tx_time", "util"):
        np.testing.assert_allclose(
            np.asarray(getattr(sh, f)), np.asarray(getattr(ref, f)),
            atol=1e-4, err_msg=f"faults: sharded vs single-device {f}")
    np.testing.assert_allclose(sh.consensus_err, ref.consensus_err, rtol=1e-5)


def check_vmap():
    """vmap composes with the shard_map engine: two (policy, seed) cells of
    an m=8 fleet on 2 shards, batched into one jit(vmap(engine)) program,
    equal the same cells run one at a time through jit(engine)."""
    import jax
    import jax.numpy as jnp

    from repro.core import triggers
    from repro.fl.simulator import make_engine

    assert jax.device_count() >= 2, jax.device_count()
    M, T, DIM = 8, 6, 24
    x, y = image_dataset(600, seed=0, dim=DIM)
    parts = by_labels(y, M, 3)
    graph = make_process(M, "rgg", time_varying="edge_dropout", drop=0.3,
                         seed=0)
    sim = SimConfig(m=M, iters=T, dim=DIM, batch=8, r=50.0, seed=0,
                    trace="summary", mix_impl="sharded", shards=2)
    eng, _ = make_engine(sim, graph, T=T, eval_every=3, x=x, y=y,
                         eval_fn=None)
    cells = [("efhc", 0), ("gossip", 1)]
    pol = jnp.asarray([triggers.policy_index(p) for p, _ in cells], jnp.int32)
    seeds = jnp.asarray([s for _, s in cells], jnp.int32)
    idx = jnp.asarray(np.stack([
        FederatedBatches(x, y, parts, sim.batch, seed=s).stage(T)
        for _, s in cells]))
    batched = jax.device_get(jax.jit(jax.vmap(eng))(pol, seeds, idx))
    solo = jax.jit(eng)
    for c in range(len(cells)):
        want = jax.device_get(solo(pol[c], seeds[c], idx[c]))
        for f, a in want.items():
            assert np.array_equal(np.asarray(batched[f][c]), np.asarray(a)), \
                f"cell {cells[c]}: vmap(sharded engine) != solo on {f}"


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "parity"
    {"golden": check_golden, "parity": check_parity,
     "fabrics": check_fabrics, "faults": check_faults,
     "vmap": check_vmap}[mode]()
    print("SHARDED-WORKER-OK")
