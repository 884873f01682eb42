"""Sharded fleet engine: shard-plan staging invariants and the S=1
in-process slice of the parity contract (DESIGN.md "Sharded fleet engine").

The multi-device halves of the acceptance criteria -- the m=8 golden
trajectory and m=256 sharded==single-device parity on 8 forced host
devices -- run in subprocesses from tests/test_golden_trajectory.py and
tests/test_scan_parity.py (XLA_FLAGS must be set before jax imports, so
the already-imported in-process jax cannot host them).  Everything here
runs on however many devices the suite happens to have.
"""
import dataclasses
import time

import numpy as np
import pytest

from repro.core import topology
from repro.core.topology import fleet_radius, make_process, shard_plan
from repro.data.loader import FederatedBatches
from repro.data.partition import by_labels
from repro.data.synthetic import image_dataset
from repro.fl.simulator import SimConfig, run
from repro.fl.sweep import run_sweep

M, T, DIM, EVAL_EVERY = 8, 12, 24, 5
# the sharded engine (scan inside shard_map) and the sparse engine are
# different compiled programs: XLA may fuse and reorder their float
# reductions differently, a few ULP over the horizon (1 observed on jax 0.9.0)
CROSS_PROGRAM_ULP = 64


@pytest.fixture(scope="module")
def setup():
    x, y = image_dataset(600, seed=0, dim=DIM)
    parts = by_labels(y, M, 3)
    graph = make_process(M, "rgg", time_varying="edge_dropout", drop=0.3,
                         seed=0)
    sim = SimConfig(m=M, iters=T, dim=DIM, batch=8, r=50.0, seed=0,
                    trace="summary")
    batches = lambda: FederatedBatches(x, y, parts, sim.batch, seed=2)
    return sim, graph, batches


# ----------------------------------------------------------- shard plan ---

@pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
def test_shard_plan_halo_tables_reconstruct_neighbors(n_shards):
    """Brute-force oracle: replaying the halo exchange on global ids must
    land every real neighbor slot on its own global id -- send_idx, the
    all-gather layout, recv_src, and nbr_loc compose to the identity."""
    g = make_process(64, "rgg", time_varying="edge_dropout", drop=0.3, seed=0)
    plan = shard_plan(g.edges, n_shards, coords=g.coords)
    nl = topology.neighbor_list_from_edges(g.edges)
    ms = plan.ms
    assert plan.m == 64 and ms * n_shards == 64
    send_gid_flat = np.full(n_shards * plan.b_max, -1, np.int64)
    for t in range(n_shards):
        send_gid_flat[t * plan.b_max: t * plan.b_max + plan.n_send[t]] = \
            plan.owned[t][plan.send_idx[t][: plan.n_send[t]]]
    for s in range(n_shards):
        buf_gid = np.concatenate(
            [plan.owned[s], np.full(plan.h_max, -1, np.int64)])
        buf_gid[ms: ms + plan.n_halo[s]] = \
            send_gid_flat[plan.recv_src[s][: plan.n_halo[s]]]
        got = buf_gid[plan.nbr_loc[s]]
        assert ((got == plan.nbr_gid[s]) | ~plan.mask[s]).all()
        # the per-shard rows are exactly the owned rows of the global ELL
        assert (plan.nbr_gid[s] == nl.idx[plan.owned[s]]).all()
        assert (plan.mask[s] == nl.mask[plan.owned[s]]).all()
    # owned is a permutation of the fleet and inv_perm inverts it
    perm = plan.owned.reshape(-1)
    assert np.array_equal(np.sort(perm), np.arange(64))
    assert np.array_equal(perm[plan.inv_perm], np.arange(64))


def test_shard_plan_rejects_indivisible_fleet():
    g = make_process(10, "ring")
    with pytest.raises(ValueError, match="divisible"):
        shard_plan(g.edges, 3)


def test_shard_plan_morton_order_shrinks_the_boundary():
    """The point of the spatial (Z-order) partition: RGG shards become
    geometrically compact blocks, so only a thin boundary strip is
    exchanged per iteration.  Contiguous id blocks on the same fabric are
    all boundary (RGG ids carry no locality)."""
    g = make_process(4096, "rgg", radius=fleet_radius(4096), seed=0)
    morton = shard_plan(g.edges, 8, coords=g.coords)
    blocks = shard_plan(g.edges, 8)
    assert morton.boundary_frac < 0.35
    assert morton.boundary_frac < 0.5 * blocks.boundary_frac


def test_shard_plan_staging_is_edge_native_at_m16384():
    """Fleet-scale staging bound: the plan builds from the edge list in
    O(E log E) host time with (S, ms, d_max)-sized tables -- nothing
    densifies an (m, m) matrix (that would be 256 M bools here)."""
    m = 16384
    g = make_process(m, "rgg", radius=fleet_radius(m), seed=0)
    t0 = time.perf_counter()
    plan = shard_plan(g.edges, 8, coords=g.coords)
    elapsed = time.perf_counter() - t0
    assert elapsed < 30.0, f"shard_plan took {elapsed:.1f}s at m={m}"
    assert plan.nbr_loc.shape == (8, m // 8, plan.d_max)
    # halo tables scale with the boundary, not the fleet
    assert plan.h_max < plan.ms
    assert plan.boundary_frac < 0.35


def test_ring_fleet_prefers_contiguous_blocks():
    """Without coords the plan falls back to contiguous id blocks -- for a
    ring that is the optimal cut: exactly 2 boundary rows per shard."""
    g = make_process(64, "ring")
    assert g.coords is None
    plan = shard_plan(g.edges, 4)
    assert (plan.n_send == 2).all() and (plan.n_halo == 2).all()


# ------------------------------------------------- engine routing (S=1) ---

def test_sharded_engine_matches_sparse_at_one_shard(setup):
    """The S=1 slice of the acceptance parity: integer channels exact,
    float channels to CROSS_PROGRAM_ULP, and the hierarchical consensus_err
    to fp32 summation-order tolerance."""
    sim, graph, batches = setup
    ref = run(dataclasses.replace(sim, mix_impl="sparse"), graph, batches(),
              None, eval_every=EVAL_EVERY)
    sh = run(dataclasses.replace(sim, mix_impl="sharded", shards=1), graph,
             batches(), None, eval_every=EVAL_EVERY)
    for f in ("v", "comm_count", "deg"):
        assert (np.asarray(getattr(sh, f))
                == np.asarray(getattr(ref, f))).all(), f
    for f in ("loss", "tx_time", "util", "bandwidths"):
        np.testing.assert_array_max_ulp(np.asarray(getattr(sh, f)),
                                        np.asarray(getattr(ref, f)),
                                        maxulp=CROSS_PROGRAM_ULP)
    np.testing.assert_allclose(sh.consensus_err, ref.consensus_err,
                               rtol=1e-5)


def test_sharded_sweep_grid_matches_single_runs(setup):
    """run_sweep routes sharded configs through the serial cell loop; each
    cell must equal its standalone run exactly (shared engine cache)."""
    sim, graph, batches = setup
    cfg = dataclasses.replace(sim, mix_impl="sharded", shards=1)
    res = run_sweep(cfg, graph, lambda s: batches(), None,
                    seeds=(0,), policies=("efhc", "gossip"),
                    eval_every=EVAL_EVERY)
    for policy in res.policies:
        single = run(dataclasses.replace(cfg, policy=policy), graph,
                     batches(), None, eval_every=EVAL_EVERY)
        cell = res.result(0, policy)
        for f in ("v", "comm_count", "deg", "loss", "tx_time", "util",
                  "consensus_err", "bandwidths"):
            assert (np.asarray(getattr(cell, f))
                    == np.asarray(getattr(single, f))).all(), (policy, f)


def test_vmap_over_two_shard_engine_matches_solo_cells():
    """jit(vmap(engine)) over the shard_map engine on 2 forced host devices
    equals each cell run alone, on every channel (tests/sharded_worker.py
    vmap; subprocess, because the device count is fixed at jax start-up)."""
    import os
    import pathlib
    import subprocess
    import sys

    worker = pathlib.Path(__file__).parent / "sharded_worker.py"
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    proc = subprocess.run([sys.executable, str(worker), "vmap"], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0 and "SHARDED-WORKER-OK" in proc.stdout, \
        f"vmap worker failed:\n{proc.stdout}\n{proc.stderr}"


def test_sharded_engine_requires_summary_trace(setup):
    sim, graph, batches = setup
    with pytest.raises(ValueError, match="summary"):
        run(dataclasses.replace(sim, mix_impl="sharded", shards=1,
                                trace="full"),
            graph, batches(), None, eval_every=EVAL_EVERY)


def test_sharded_engine_refuses_python_loop(setup):
    sim, graph, batches = setup
    with pytest.raises(ValueError, match="sharded"):
        run(dataclasses.replace(sim, mix_impl="sharded", shards=1), graph,
            batches(), None, eval_every=EVAL_EVERY, engine="python")


def test_fleet_mesh_explains_missing_devices():
    import jax

    from repro.launch.mesh import make_fleet_mesh

    too_many = jax.device_count() + 1
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_fleet_mesh(too_many)
