"""Scenario-serving subsystem (ISSUE 8): request validation, compatibility
grouping, batched-vs-solo bit-identity, and cache observability.

The parity tests ride the repo's standing pattern (tests/test_scan_parity):
every channel of a batched cell must match its solo counterpart -- here
BIT-identical, since the vmapped grid runs the same compiled arithmetic."""
import dataclasses
import itertools

import numpy as np
import pytest

from repro import api
from repro.fl import service as service_mod
from repro.fl import simulator

BASE = dict(m=8, dim=16, n_train=320, n_test=80, iters=8, eval_every=3,
            batch=8)

CHANNELS = ("loss", "acc", "tx_time", "util", "v", "comm_count", "deg",
            "consensus_err", "bandwidths")


def assert_bit_identical(got, want, label=""):
    assert got.model_dim == want.model_dim
    for f in CHANNELS:
        assert np.array_equal(np.asarray(getattr(got, f)),
                              np.asarray(getattr(want, f))), f"{label}: {f}"


INT_CHANNELS = ("v", "comm_count", "deg")
FLOAT_CHANNELS = ("loss", "acc", "tx_time", "util", "consensus_err",
                  "bandwidths")
# Two DIFFERENT compiled programs (a vmapped sweep grid vs the service's
# padded grid) run the same arithmetic, but XLA may fuse and reorder the
# float reductions differently in each; over the horizon that compounds to
# a few ULP (14 observed on jax 0.9.0).  Integer/bool channels stay exact.
CROSS_PROGRAM_ULP = 64


def assert_cross_program_match(got, want, label=""):
    assert got.model_dim == want.model_dim
    for f in INT_CHANNELS:
        assert np.array_equal(np.asarray(getattr(got, f)),
                              np.asarray(getattr(want, f))), f"{label}: {f}"
    for f in FLOAT_CHANNELS:
        np.testing.assert_array_max_ulp(
            np.asarray(getattr(got, f), np.float32),
            np.asarray(getattr(want, f), np.float32),
            maxulp=CROSS_PROGRAM_ULP)


# ------------------------------------------------------------ validation --

def test_spec_defaults_valid_and_frozen():
    spec = api.ScenarioSpec()
    assert spec.seeds == (0,)
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.m = 4


def test_spec_seed_list_normalized_to_tuple():
    assert api.ScenarioSpec(seeds=[3, 1]).seeds == (3, 1)


@pytest.mark.parametrize("field,bad,allowed", [
    ("topology", "smallworld", str(service_mod.TOPOLOGIES)),
    ("time_varying", "churn", str(service_mod.TIME_VARYING)),
    ("partition", "iid", str(service_mod.PARTITIONS)),
    # SimConfig-level fields must reject through the spec too
    ("policy", "efch", "efhc"),
    ("model", "resnet", "svm"),
    ("mix_impl", "sparse_ell", "sparse"),
    ("trace", "fulll", "summary"),
    ("optimizer", "adamw", "sgd"),
])
def test_spec_rejects_unknown_values_naming_allowed(field, bad, allowed):
    with pytest.raises(ValueError) as ei:
        api.ScenarioSpec(**{field: bad})
    assert bad in str(ei.value) and allowed in str(ei.value)


@pytest.mark.parametrize("kw", [
    dict(seeds=()), dict(eval_every=0), dict(n_train=0), dict(n_test=0),
    dict(m=0), dict(iters=0), dict(shards=2, mix_impl="dense"),
    dict(mix_impl="sharded", shards=2, trace="full"),
])
def test_spec_rejects_illegal_combos(kw):
    with pytest.raises(ValueError):
        api.ScenarioSpec(**kw)


def test_service_rejects_non_spec_and_bad_max_cells():
    with pytest.raises(TypeError, match="ScenarioSpec"):
        api.ScenarioService().submit({"m": 8})
    with pytest.raises(ValueError, match="max_cells"):
        api.ScenarioService(max_cells=0)


def test_provider_rejects_token_models():
    with pytest.raises(ValueError, match="provider"):
        api.simulate(api.ScenarioSpec(model="tiny_transformer", dim=16,
                                      n_classes=32))


# ----------------------------------------------------- signature/grouping --

def test_signature_ignores_exactly_the_cell_fields():
    """Property-style sweep: toggling any cell-varying field keeps the
    signature; toggling any compile-shaping field changes it."""
    base = api.ScenarioSpec(**BASE)
    cell_variants = dict(policy="gossip", seeds=(4, 5), sample_seed=9,
                         deadline_s=30.0)
    for f, v in cell_variants.items():
        other = dataclasses.replace(base, **{f: v})
        assert other.signature() == base.signature(), f
    shaping_variants = dict(
        m=10, topology="ring", time_varying="static", drop=0.1, cycle_len=3,
        graph_seed=1, model="mlp", dim=20, n_classes=5, n_train=300,
        n_test=100, data_seed=1, partition="dirichlet", labels_per_device=2,
        dirichlet_alpha=0.5, smooth=1, r=10.0, b_mean=1000.0, sigma_n=0.5,
        alpha0=0.2, optimizer="adam", batch=4, iters=6, mix_impl="sparse",
        trace="packed", eval_every=2, churn_rate=0.1, recover_rate=0.25,
        straggle_rate=0.1, bw_walk=0.05, budget_bytes=1e6,
        cluster_fail_rate=0.05, cluster_recover_rate=0.5, partition_start=3,
        partition_len=2, flap_rate=0.1, flap_len=4, crash_rate=0.05,
        rejoin_rate=0.5, warm_start=True, watchdog_window=4,
        watchdog_nprop=8)
    for f, v in shaping_variants.items():
        other = dataclasses.replace(base, **{f: v})
        assert other.signature() != base.signature(), f
    # shards can only legally vary under the sharded engine
    sharded = dataclasses.replace(base, mix_impl="sharded", trace="summary")
    assert (dataclasses.replace(sharded, shards=2).signature()
            != sharded.signature())
    # the sweep above must cover every declared field
    covered = set(cell_variants) | set(shaping_variants) | {"shards"}
    assert covered == {f.name for f in dataclasses.fields(base)}


def test_incompatible_specs_never_co_batch():
    """Requests only share a launch when their signatures match, for every
    pairing in a small property grid."""
    grid = [api.ScenarioSpec(**BASE, policy=p, r=r, seeds=(s,))
            for p, r, s in itertools.product(("efhc", "gossip"),
                                             (50.0, 10.0), (0, 1))]
    svc = api.ScenarioService(max_cells=16)
    reports = svc.serve(grid)
    by_launch = {}
    for rep in reports:
        by_launch.setdefault(rep.launch_id, []).append(rep.spec)
    assert len(by_launch) == 2  # exactly one launch per distinct r
    for specs in by_launch.values():
        sigs = {s.signature() for s in specs}
        assert len(sigs) == 1, "co-batched requests must share a signature"
        assert len(specs) == 4  # all 4 compatible requests rode together


# ------------------------------------------------------------ bit-parity --

@pytest.fixture(scope="module")
def served():
    """A mixed 3-request / 2-signature batch served with forced bucketing
    (max_cells=2 splits signature A's 3 cells over two launches)."""
    specs = [api.ScenarioSpec(**BASE, policy="efhc", seeds=(0, 1)),
             api.ScenarioSpec(**BASE, policy="gossip", seeds=(2,)),
             api.ScenarioSpec(**BASE, policy="efhc", r=10.0, seeds=(0,))]
    svc = api.ScenarioService(max_cells=2)
    return specs, svc.serve(specs), svc


def test_batched_results_bit_identical_to_solo(served):
    specs, reports, _ = served
    for spec, rep in zip(specs, reports):
        for s in spec.seeds:
            solo = api.simulate(spec, seed=s)
            assert_bit_identical(rep.results[s], solo,
                                 f"req {rep.request_id} seed {s}")


def test_report_accounting_shape(served):
    specs, reports, svc = served
    assert [r.request_id for r in reports] == [0, 1, 2]
    for rep in reports:
        assert set(rep.results) == set(rep.spec.seeds)
        assert set(rep.tx) == set(rep.spec.seeds)
        assert rep.queue_wait_s >= 0 and rep.run_s > 0
        for s, tx in rep.tx.items():
            assert tx.tx_time == pytest.approx(
                float(rep.results[s].tx_time.sum()))
    stats = svc.stats()
    assert stats.requests == 3 and stats.cells == 4
    assert stats.launches == 3  # sig A split in two (max_cells=2) + sig B
    # the split rounds ran at different bucket sizes (2 cells, then 1), so
    # no program reuse yet -- round 2 below is what must hit
    assert (stats.program_hits, stats.program_misses) == (0, 3)


def test_round2_hits_engine_and_program_cache(served):
    specs, _, svc = served
    rep = svc.serve([dataclasses.replace(specs[0], policy="zero",
                                         seeds=(9, 11))])[0]
    assert rep.engine_cache_hit and rep.program_cache_hit
    assert_bit_identical(
        rep.results[9],
        api.simulate(dataclasses.replace(specs[0], policy="zero"), seed=9),
        "round-2 cell")


# ------------------------------------------------------- failure isolation --

def test_poisoned_spec_mid_batch_keeps_the_queue_draining():
    """Regression (ISSUE 9 satellite): ``serve`` drains via
    ``while queue: poll()``, so an exception escaping one round used to
    abort the loop and strand every request queued behind it.  A failed
    round must come back as error-tagged reports while the healthy rounds
    before AND after it complete, bit-identical to solo."""
    svc = api.ScenarioService(max_cells=4)
    healthy1 = api.ScenarioSpec(**BASE, seeds=(0, 1))
    # constructs fine (registry-valid model) but the synthetic provider
    # raises at staging time: the natural poisoned-round failure
    poisoned = api.ScenarioSpec(**BASE, model="tiny_transformer",
                                n_classes=32)
    healthy2 = api.ScenarioSpec(**BASE, r=10.0, seeds=(1,))
    reports = svc.serve([healthy1, poisoned, healthy2])

    assert [r.request_id for r in reports] == [0, 1, 2]
    bad = reports[1]
    assert not bad.ok and "provider" in bad.error
    assert bad.results == {} and bad.tx == {} and bad.launch_id == -1
    with pytest.raises(RuntimeError, match="request 1 failed"):
        bad.result()
    assert svc.stats().failures == 1
    for rep in (reports[0], reports[2]):
        assert rep.ok and rep.error is None
        for s in rep.spec.seeds:
            assert_bit_identical(rep.results[s], api.simulate(rep.spec, seed=s),
                                 f"healthy req {rep.request_id} seed {s}")


# --------------------------------------------------------- cache counters --

def test_engine_cache_stats_observable():
    simulator._ENGINE_CACHE.clear(reset_stats=True)
    spec = api.ScenarioSpec(**{**BASE, "dim": 12}, policy="efhc")
    api.simulate(spec)
    s1 = simulator.engine_cache_stats()
    assert (s1.misses, s1.entries) == (1, 1) and s1.key_bytes > 0
    api.simulate(spec, seed=5)  # same engine, traced seed
    s2 = simulator.engine_cache_stats()
    assert s2.hits == s1.hits + 1 and s2.misses == s1.misses
    assert 0 < s2.hit_rate < 1
    d = s2.as_dict()
    assert d["entries"] == 1 and d["hits"] == s2.hits


def test_sweep_entry_point_matches_service_cells():
    spec = api.ScenarioSpec(**BASE, seeds=(0,))
    grid = api.sweep(spec, policies=("efhc", "gossip"))
    svc = api.ScenarioService(max_cells=4)
    reports = svc.serve([dataclasses.replace(spec, policy=p)
                         for p in ("efhc", "gossip")])
    for rep, policy in zip(reports, ("efhc", "gossip")):
        assert_cross_program_match(rep.results[0], grid.result(0, policy),
                                   f"sweep vs service {policy}")


# ------------------------------------------------------ service hardening --
# ISSUE 10: deadlines, bounded retry-with-backoff, NaN/Inf quarantine.

def test_deadline_s_is_queue_policy_not_compile_shaping():
    base = api.ScenarioSpec(**BASE)
    with_deadline = dataclasses.replace(base, deadline_s=5.0)
    assert with_deadline.signature() == base.signature(), \
        "deadline_s must not split batch signatures"
    with pytest.raises(ValueError, match="deadline_s"):
        api.ScenarioSpec(**BASE, deadline_s=-1.0)


def test_expired_request_is_answered_not_launched():
    import time

    svc = api.ScenarioService(max_cells=4)
    rid = svc.submit(api.ScenarioSpec(**BASE, deadline_s=1e-9))
    ok_rid = svc.submit(api.ScenarioSpec(**BASE))
    time.sleep(0.01)
    reports = svc.serve()
    by_rid = {r.request_id: r for r in reports}
    bad = by_rid[rid]
    assert not bad.ok and "DeadlineExceeded" in bad.error
    assert bad.results == {} and bad.launch_id == -1
    assert by_rid[ok_rid].ok, "no-deadline request must still be served"
    assert svc.stats().deadline_expired == 1
    assert svc.stats().as_dict()["deadline_expired"] == 1


class _FlakyProvider:
    """Fails the first ``n_fail`` staging calls, then delegates to the
    default synthetic provider -- the transient-infrastructure-error stand-in
    the retry loop exists for."""

    def __init__(self, n_fail):
        self.n_fail = n_fail
        self.calls = 0

    def __call__(self, spec):
        self.calls += 1
        if self.calls <= self.n_fail:
            raise OSError("transient staging failure")
        return service_mod._DEFAULT_PROVIDER(spec)


def test_transient_failure_retries_and_recovers():
    provider = _FlakyProvider(n_fail=1)
    svc = api.ScenarioService(provider, max_cells=4, max_retries=2,
                              retry_backoff_s=0.0)
    spec = api.ScenarioSpec(**BASE, seeds=(0,))
    reports = svc.serve([spec])
    assert len(reports) == 1 and reports[0].ok
    assert reports[0].retries == 1, "one failed round before the success"
    stats = svc.stats()
    assert stats.retries == 1 and stats.failures == 0
    assert_bit_identical(reports[0].results[0], api.simulate(spec, seed=0),
                         "post-retry cell")


def test_persistent_failure_exhausts_retries_then_errors():
    provider = _FlakyProvider(n_fail=100)
    svc = api.ScenarioService(provider, max_cells=4, max_retries=2,
                              retry_backoff_s=0.0)
    reports = svc.serve([api.ScenarioSpec(**BASE)])
    assert len(reports) == 1 and not reports[0].ok
    assert "transient staging failure" in reports[0].error
    assert reports[0].retries == 2
    stats = svc.stats()
    assert stats.retries == 2 and stats.failures == 1
    assert provider.calls == 3  # initial + 2 retries


def test_retry_knobs_validate():
    with pytest.raises(ValueError, match="max_retries"):
        api.ScenarioService(max_retries=-1)
    with pytest.raises(ValueError, match="retry_backoff_s"):
        api.ScenarioService(retry_backoff_s=-0.1)


class _PoisonedProvider:
    """The default synthetic dataset with one training row driven to Inf:
    only the cells whose sampler stream draws that row diverge."""

    def __init__(self, row):
        self.row = row
        self._cache = {}

    def __call__(self, spec):
        k = service_mod.SyntheticProvider.key(spec)
        if k not in self._cache:
            ds = service_mod._DEFAULT_PROVIDER(spec)
            x = np.array(ds.x)
            x[self.row] = np.inf
            self._cache[k] = dataclasses.replace(ds, x=x)
        return self._cache[k]


def test_nan_quarantine_isolates_the_diverged_cell():
    """A cell that samples the poisoned row goes non-finite and is
    quarantined; a co-batched cell of the SAME request that never touches
    the row comes back BIT-identical to its run against the same provider
    -- quarantine must be pure filtering, not recomputation."""
    row = 7
    provider = _PoisonedProvider(row)
    probe = api.ScenarioSpec(**BASE, seeds=(0,))
    ds = provider(probe)
    hit = miss = None
    for s in range(64):
        idx = probe.batches(s, ds).stage(probe.iters)  # (T, m, batch)
        per_step = (idx == row).reshape(idx.shape[0], -1).any(1)
        if hit is None and per_step[: probe.iters // 2].any():
            hit = s  # diverges early: non-finite before the recorded evals end
        if miss is None and not per_step.any():
            miss = s
        if hit is not None and miss is not None:
            break
    assert hit is not None and miss is not None, \
        "need both a poisoned and a clean sampler stream among seeds 0..63"

    spec = api.ScenarioSpec(**BASE, seeds=(hit, miss))
    svc = api.ScenarioService(provider, max_cells=4)
    rep = svc.serve([spec])[0]
    assert rep.ok, "quarantine is per-cell, not a request failure"
    assert rep.quarantined == (hit,)
    assert set(rep.results) == {miss} and set(rep.tx) == {miss}
    with pytest.raises(RuntimeError, match="quarantined"):
        rep.result(hit)
    solo = service_mod.solo_run(spec, seed=miss, provider=provider)
    assert_bit_identical(rep.results[miss], solo, "clean cell next to NaN")
    assert svc.stats().quarantined == 1
    # the diverged run really is non-finite (the quarantine was warranted)
    bad = service_mod.solo_run(spec, seed=hit, provider=provider)
    assert not np.isfinite(bad.loss).all()
