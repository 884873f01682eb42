"""Resource-dynamics subsystem (ISSUE 9): churn, stragglers, depleting
budgets, and live-bandwidth triggers -- plus the tentpole's hard promise
that a zero-churn / static-budget config stays BIT-identical to the golden
trajectories the pre-resource engines produced.

Layered like the subsystem itself: core ``ResourceConfig``/``evolve``
semantics first, then exact engine-level behavior (liveness masks Event 2,
budgets deplete and silence the fleet, stragglers skip Event 4), then the
end-to-end plumbing (sweep channels, ScenarioService parity, engine-cache
seed keying).
"""
import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import resources
from repro.core.accounting import model_bytes
from repro.core.topology import make_process
from repro.data.loader import FederatedBatches
from repro.data.partition import by_labels
from repro.data.synthetic import image_dataset
from repro.fl.simulator import SimConfig, run
from repro.fl.sweep import run_sweep

GOLDEN = pathlib.Path(__file__).parent / "golden" / "efhc_m8_trajectory.json"
M, T, DIM = 8, 18, 24  # the golden run's canonical shape


def _golden_setup(**sim_kw):
    x, y = image_dataset(600, seed=0, dim=DIM)
    parts = by_labels(y, M, 3)
    graph = make_process(M, "rgg", time_varying="edge_dropout", drop=0.3,
                         seed=0)
    sim = SimConfig(m=M, iters=T, dim=DIM, batch=8, r=50.0, seed=0, **sim_kw)
    batches = FederatedBatches(x, y, parts, sim.batch, seed=2)
    return sim, graph, batches


# ------------------------------------------------------------ core config --

def test_resource_config_disabled_at_defaults():
    cfg = resources.ResourceConfig()
    assert not cfg.enabled
    # knobs that cannot matter while everything else is off stay disabled
    assert not resources.ResourceConfig(recover_rate=0.9).enabled
    assert not resources.ResourceConfig(bw_revert=0.7).enabled
    for kw in (dict(churn_rate=0.1), dict(straggle_rate=0.1),
               dict(bw_walk=0.1), dict(budget_bytes=1.0)):
        assert resources.ResourceConfig(**kw).enabled, kw


@pytest.mark.parametrize("kw,name", [
    (dict(churn_rate=1.5), "churn_rate"),
    (dict(churn_rate=-0.1), "churn_rate"),
    (dict(recover_rate=2.0), "recover_rate"),
    (dict(straggle_rate=-1.0), "straggle_rate"),
    (dict(bw_revert=1.5), "bw_revert"),
    (dict(bw_walk=-0.5), "bw_walk"),
    (dict(budget_bytes=-1.0), "budget_bytes"),
])
def test_resource_config_validates_naming_the_knob(kw, name):
    with pytest.raises(ValueError, match=name):
        resources.ResourceConfig(**kw)
    if "bw_revert" not in kw:  # SimConfig has no bw_revert knob
        # SimConfig surfaces the same validation at construction
        with pytest.raises(ValueError, match=name):
            SimConfig(**kw)


def test_evolve_churn_recover_and_bw_floor():
    m = 4096
    cfg = resources.ResourceConfig(churn_rate=0.3, recover_rate=0.4,
                                   bw_walk=2.0)
    bw0 = jnp.full((m,), 5000.0)
    up = jnp.ones((m,), bool)
    key = jax.random.PRNGKey(0)
    up1, straggle, bw1 = resources.evolve(cfg, key, up, bw0, bw0, m)
    down_frac = float(jnp.mean(~up1))
    assert abs(down_frac - 0.3) < 0.03, "churn hits ~churn_rate of up devices"
    assert not bool(straggle.any()), "straggle_rate=0 -> nobody straggles"
    # a violent walk still respects the positive floor
    assert float(bw1.min()) >= resources.BW_FLOOR_FRAC * 5000.0
    # down devices recover at ~recover_rate
    up2, _, _ = resources.evolve(cfg, jax.random.PRNGKey(1), up1, bw1, bw0, m)
    rec = float(jnp.mean(up2[~up1]))
    assert abs(rec - 0.4) < 0.05


def test_evolve_rows_slice_matches_full_fleet():
    """Positional draws: a shard evaluating only its owned rows realizes
    the identical per-device stream (the sharded bit-compat contract)."""
    m = 64
    cfg = resources.ResourceConfig(churn_rate=0.4, straggle_rate=0.3,
                                   bw_walk=0.2)
    bw0 = jnp.linspace(1000.0, 9000.0, m)
    up = jnp.ones((m,), bool)
    key = jax.random.PRNGKey(3)
    full = resources.evolve(cfg, key, up, bw0, bw0, m)
    rows = jnp.asarray([5, 17, 40, 63])
    part = resources.evolve(cfg, key, up[rows], bw0[rows], bw0[rows], m,
                            rows=rows)
    for f, p in zip(full, part):
        assert np.array_equal(np.asarray(f)[np.asarray(rows)], np.asarray(p))


# --------------------------------------------------- golden bit-compat ----

def test_disabled_resources_bit_identical_to_golden_trajectory():
    """The tentpole's hard constraint: a config with the resource fields
    explicitly present (but disabled) reproduces the checked-in golden
    trajectory bit-for-bit on the integer channels -- the resource plumbing
    must be structurally absent from the disabled program, not merely
    numerically quiet.  ``recover_rate`` is set off-default to pin that
    inert knobs cannot move the realization either."""
    want = json.loads(GOLDEN.read_text())
    sim, graph, batches = _golden_setup(
        churn_rate=0.0, straggle_rate=0.0, bw_walk=0.0, budget_bytes=0.0,
        recover_rate=0.9)
    assert sim.resources() is None
    res = run(sim, graph, batches, None, eval_every=5, engine="scan")
    for f in ("v", "comm_count", "deg"):
        assert np.array_equal(np.asarray(getattr(res, f), np.int64),
                              np.asarray(want[f], np.int64)), \
            f"resource plumbing shifted the golden realization: {f}"
    for f in ("loss", "tx_time", "util", "consensus_err"):
        np.testing.assert_allclose(
            np.asarray(getattr(res, f), np.float64), np.asarray(want[f]),
            rtol=2e-4, atol=2e-5, err_msg=f"{f} diverged from golden")
    np.testing.assert_allclose(res.bandwidths, np.asarray(want["bandwidths"]),
                               rtol=1e-5)
    # the channels exist and are all-zero without a resource process
    assert res.down_count.shape == (T,) and not res.down_count.any()
    assert res.exhausted_count.shape == (T,) and not res.exhausted_count.any()


# -------------------------------------------------- engine-level behavior --

def test_churn_masks_broadcasts_exactly():
    """Under policy='zero' (fire always) every up device fires and every
    down device is silent, so sum(v) + down_count == m EXACTLY per step."""
    sim, graph, batches = _golden_setup(policy="zero", churn_rate=0.3,
                                        recover_rate=0.4)
    res = run(sim, graph, batches, None, eval_every=5)
    down = res.down_count
    assert down.max() > 0, "churn_rate=0.3 over 18 iters must down someone"
    assert down.min() >= 0 and down.max() <= M
    np.testing.assert_array_equal(res.v.sum(axis=1) + down, M)
    # a down device's edges leave G^(k): fleet degree shrinks on down steps
    assert res.exhausted_count.sum() == 0  # no budget in this run


def test_budget_depletes_and_silences_the_fleet():
    """policy='zero' spends model_bytes per device-step; with a budget of
    2.5 models every device fires steps 0-2 and is exhausted from step 3 on
    -- exact, not statistical (budget is checked before the debit)."""
    sim0, graph, batches = _golden_setup(policy="zero")
    n_bytes = model_bytes(DIM * 10 + 10)  # svm flat_dim at dim=24
    sim = dataclasses.replace(sim0, budget_bytes=2.5 * n_bytes)
    res = run(sim, graph, batches, None, eval_every=5)
    assert res.model_dim == DIM * 10 + 10
    np.testing.assert_array_equal(res.v.sum(axis=1),
                                  [M, M, M] + [0] * (T - 3))
    np.testing.assert_array_equal(res.exhausted_count,
                                  [0, 0, 0] + [M] * (T - 3))
    assert res.down_count.sum() == 0  # no churn in this run


def test_budget_exhaustion_quiets_efhc_through_thresholds():
    """EF-HC goes quiet *naturally*: the exhausted threshold bandwidth
    collapses (rho = 1/b explodes), so firing stops without a hard mask
    being the only line of defense."""
    sim0, graph, batches = _golden_setup(policy="efhc")
    base = run(sim0, graph, batches, None, eval_every=5)
    n_bytes = model_bytes(base.model_dim)
    sim = dataclasses.replace(sim0, budget_bytes=1.5 * n_bytes)
    res = run(sim, graph, batches, None, eval_every=5)
    assert res.exhausted_count[-1] == M, "everyone exhausts eventually"
    k_done = int(np.argmax(res.exhausted_count == M))
    assert not res.v[k_done:].any(), "no broadcasts after exhaustion"
    assert res.v.sum() < base.v.sum(), "budget must cut total broadcasts"


def test_full_straggle_equals_zero_learning_rate():
    """straggle_rate=1 skips every Event-4 update; mixing still runs, so
    the trajectory equals an alpha0=0 run of the same seed."""
    sim_a, graph, b_a = _golden_setup(policy="zero", straggle_rate=1.0)
    _, _, b_b = _golden_setup(policy="zero")
    sim_b = dataclasses.replace(sim_a, straggle_rate=0.0, alpha0=0.0)
    res_a = run(sim_a, graph, b_a, None, eval_every=5)
    res_b = run(sim_b, graph, b_b, None, eval_every=5)
    np.testing.assert_array_equal(res_a.v, res_b.v)
    np.testing.assert_allclose(res_a.loss, res_b.loss, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(res_a.consensus_err, res_b.consensus_err,
                               rtol=1e-6, atol=1e-7)


def test_bandwidth_walk_feeds_live_thresholds():
    """bw_walk changes which devices clear r * rho_i * gamma^k: the EF-HC
    event trace must move relative to the static-bandwidth run (thresholds
    read b_i^(k), not the k=0 sample)."""
    sim0, graph, batches = _golden_setup(policy="efhc")
    base = run(sim0, graph, batches, None, eval_every=5)
    _, _, batches2 = _golden_setup(policy="efhc")
    walked = run(dataclasses.replace(sim0, bw_walk=0.5), graph, batches2,
                 None, eval_every=5)
    assert (base.v != walked.v).any(), \
        "a violent bandwidth walk must move the EF-HC event trace"
    # the reported bandwidths channel stays the k=0 sample (the walk lives
    # in the scan carry)
    np.testing.assert_allclose(base.bandwidths, walked.bandwidths)


def test_python_engine_matches_scan_under_dynamics():
    """The legacy per-step loop threads the same resource state: full
    dynamics on, every channel agrees with the compiled scan engine."""
    sim, graph, b1 = _golden_setup(policy="efhc", churn_rate=0.25,
                                   straggle_rate=0.2, bw_walk=0.1,
                                   budget_bytes=3e6)
    _, _, b2 = _golden_setup()
    scan = run(sim, graph, b1, None, eval_every=5, engine="scan")
    ref = run(sim, graph, b2, None, eval_every=5, engine="python")
    for f in ("v", "comm_count", "deg", "down_count", "exhausted_count"):
        np.testing.assert_array_equal(getattr(scan, f), getattr(ref, f),
                                      err_msg=f"scan vs python: {f}")
    for f in ("loss", "tx_time", "util", "consensus_err"):
        np.testing.assert_allclose(getattr(scan, f), getattr(ref, f),
                                   atol=1e-4, err_msg=f"scan vs python: {f}")


def test_resource_stream_varies_with_the_run_seed():
    """Regression: the resource stream must ride the TRACED run seed, never
    a static config-seed fold baked into the compiled engine -- otherwise
    two runs differing only in seed (which share one cached compile) would
    realize the same churn."""
    sim, graph, b1 = _golden_setup(policy="zero", churn_rate=0.5)
    _, _, b2 = _golden_setup()
    r0 = run(sim, graph, b1, None, eval_every=5)
    r1 = run(dataclasses.replace(sim, seed=1), graph, b2, None, eval_every=5)
    assert (r0.down_count != r1.down_count).any(), \
        "distinct seeds realized the same churn: engine-cache aliasing"


# ----------------------------------------------------- end-to-end plumbing --

DYN = dict(m=8, dim=16, n_train=320, n_test=80, iters=10, eval_every=3,
           batch=8, churn_rate=0.25, straggle_rate=0.2, bw_walk=0.1,
           budget_bytes=2e6)

SERVICE_CHANNELS = ("loss", "acc", "tx_time", "util", "v", "comm_count",
                    "deg", "consensus_err", "bandwidths", "down_count",
                    "exhausted_count")
SERVICE_FLOAT_CHANNELS = ("loss", "acc", "tx_time", "util", "consensus_err",
                          "bandwidths")
# the service's vmapped grid and the solo engine are different compiled
# programs: XLA may fuse and reorder their float reductions differently,
# which compounds to a few ULP over the horizon (2 observed on jax 0.9.0)
CROSS_PROGRAM_ULP = 64


def test_sweep_grid_carries_resource_channels():
    sim, graph, _ = _golden_setup(churn_rate=0.25, budget_bytes=3e6)
    x, y = image_dataset(600, seed=0, dim=DIM)
    parts = by_labels(y, M, 3)
    grid = run_sweep(sim, graph,
                     lambda s: FederatedBatches(x, y, parts, sim.batch,
                                                seed=2 + s),
                     None, seeds=(0,), policies=("efhc", "zero"),
                     eval_every=5)
    assert grid.down_count.shape == (1, 2, T)
    assert grid.exhausted_count.shape == (1, 2, T)
    assert grid.down_count.max() > 0
    # result() slices the channels through to the SimResult contract, and
    # zero-policy cells keep the exact liveness identity while nobody is
    # budget-exhausted yet
    cell = grid.result(0, "zero")
    live = cell.exhausted_count == 0
    np.testing.assert_array_equal(
        cell.v.sum(axis=1)[live] + cell.down_count[live], M)


def test_service_bit_identical_to_simulate_under_dynamics():
    """The batched ScenarioService serves churn/budget/straggler scenarios
    bit-identically to the solo ``api.simulate`` path, resource channels
    included (the acceptance gate's 'both entry points' clause)."""
    spec = api.ScenarioSpec(**DYN, policy="efhc", seeds=(0, 1))
    svc = api.ScenarioService(max_cells=4)
    rep = svc.serve([spec])[0]
    assert rep.ok
    for s in spec.seeds:
        solo = api.simulate(spec, seed=s)
        got = rep.results[s]
        assert got.model_dim == solo.model_dim
        for f in SERVICE_CHANNELS:
            if f in SERVICE_FLOAT_CHANNELS:
                np.testing.assert_array_max_ulp(
                    np.asarray(getattr(got, f), np.float32),
                    np.asarray(getattr(solo, f), np.float32),
                    maxulp=CROSS_PROGRAM_ULP)
                continue
            assert np.array_equal(np.asarray(getattr(got, f)),
                                  np.asarray(getattr(solo, f))), \
                f"service vs solo under dynamics: seed {s}, {f}"
        assert rep.tx[s].down_device_steps == int(solo.down_count.sum())
        assert rep.tx[s].exhausted_device_steps == int(
            solo.exhausted_count.sum())


def test_spec_resource_fields_reach_the_engine():
    spec = api.ScenarioSpec(**DYN, seeds=(0,))
    sim = spec.to_sim()
    rcfg = sim.resources()
    assert rcfg is not None and rcfg.churn_rate == 0.25
    res = api.simulate(spec)
    assert res.down_count.max() > 0


def test_new_fabrics_and_dynamics_parity_at_m256_on_8_devices():
    """ISSUE 9 acceptance at fleet scale, in a subprocess (the forced
    8-device count must be set before jax initializes): scale-free and
    clustered fabrics agree dense vs sparse vs sharded at m=256, and the
    sharded engine realizes the identical resource stream under full
    dynamics (see sharded_worker.check_fabrics)."""
    import os
    import subprocess
    import sys

    worker = pathlib.Path(__file__).parent / "sharded_worker.py"
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    proc = subprocess.run([sys.executable, str(worker), "fabrics"],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0 and "SHARDED-WORKER-OK" in proc.stdout, \
        f"fabric parity worker failed:\n{proc.stdout}\n{proc.stderr}"
