"""Scenario service: continuous-batched what-if sweeps behind one API.

The ROADMAP's "millions of users" workload is operational, not academic:
thousands of concurrent *what-if* requests against one resident fleet
engine -- which trigger policy / threshold / fabric wins under my resource
budget?  Each request is a ``ScenarioSpec`` (fleet fabric, model, trigger
policy, threshold, horizon, seeds); the service answers them the way a
model server answers inference traffic:

* **Validated request schema** -- ``ScenarioSpec`` is frozen and fail-fast:
  every registry-valued field is checked at construction with the allowed
  values named, and illegal combinations (``shards`` without the sharded
  engine, link-matrix traces on a sharded run) are rejected before any
  compile happens.  Field validation is shared with ``SimConfig`` (the spec
  builds one in ``__post_init__``).
* **Continuous batching** -- queued requests are grouped by their
  *compatibility signature* (every spec field except ``policy``/``seeds``/
  ``sample_seed``: same fabric, model, horizon, trace and mix impl mean the
  same compiled engine) and each group launches as ONE ``jit(vmap(engine))``
  call over the flattened (request, seed) cells.  Policy and seed enter the
  engine as *traced* arguments (DESIGN.md "Policy dispatch table"), so
  heterogeneous policies and seeds ride a single program.  Per-cell results
  are bit-identical to solo runs (pinned by tests/test_service.py).
* **Compile reuse** -- engines come from the simulator's value-keyed LRU
  (``simulator.engine_cache_stats`` makes hits observable); the vmapped
  grid is cached per engine, and cell batches are padded up to power-of-two
  buckets so a signature that recurs with a different request count still
  reuses its compiled program instead of triggering a shape-change
  recompile.
* **Per-request accounting** -- each ``ScenarioReport`` carries queue-wait /
  staging / run latency, cache-hit flags, and a summary-trace-native
  ``TxSummary`` (``core.accounting``) per seed.

``repro.api`` re-exports the stable entry points (``ScenarioSpec``,
``simulate``, ``sweep``, ``serve``); ``launch/serve.py`` is the CLI driver.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import OrderedDict, deque
from typing import Callable, Sequence

import jax
import numpy as np

from repro.core import accounting, triggers
from repro.core.topology import GraphProcess, make_process
from repro.data.loader import FederatedBatches
from repro.data.partition import by_labels, dirichlet
from repro.data.synthetic import image_dataset
from repro.fl import simulator, sweep as sweep_mod
from repro.fl.simulator import EvalFn, SimConfig, SimResult, make_eval_fn

TOPOLOGIES: tuple[str, ...] = ("rgg", "er", "ring", "complete",
                               "scale_free", "clustered")
TIME_VARYING: tuple[str, ...] = ("static", "edge_dropout", "partition_cycle")
PARTITIONS: tuple[str, ...] = ("by_labels", "dirichlet")

# spec fields a batch group may vary per cell: the trigger policy and the
# PRNG seed are *traced* engine arguments, and the sampler seed only shapes
# the staged index array (also traced).  ``deadline_s`` is pure queue
# policy -- it never touches the compiled program, so two requests that
# differ only in deadline still co-batch.  Everything else is
# compile-shaping and defines the compatibility signature.
CELL_FIELDS: tuple[str, ...] = ("policy", "seeds", "sample_seed",
                                "deadline_s")


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """One validated what-if request (the public schema of ``repro.api``).

    Groups of specs sharing ``signature()`` are served in one vmapped
    launch; ``seeds`` fans a request out to one cell per seed (data
    sampling, bandwidths, and model init all re-randomize per seed).
    """

    # --- fleet fabric ----------------------------------------------------
    m: int = 10
    topology: str = "rgg"  # see TOPOLOGIES
    time_varying: str = "edge_dropout"  # see TIME_VARYING
    drop: float = 0.3
    cycle_len: int = 2
    graph_seed: int = 0
    # --- model + data ----------------------------------------------------
    model: str = "svm"  # any repro.fl.modelspec registry name
    dim: int = 784
    n_classes: int = 10
    n_train: int = 4000
    n_test: int = 800
    data_seed: int = 0
    partition: str = "by_labels"  # see PARTITIONS
    labels_per_device: int = 1
    dirichlet_alpha: float = 0.3
    smooth: int = 0  # box-blur radius for conv-friendly synthetic images
    # --- algorithm -------------------------------------------------------
    policy: str = "efhc"  # traced: may vary within a batch group
    r: float = 50.0  # trigger threshold scale (compile-time constant)
    b_mean: float = 5000.0
    sigma_n: float = 0.9
    alpha0: float = 0.1
    optimizer: str = "sgd"
    batch: int = 16
    # --- resource dynamics (compile-shaping; zero defaults = disabled) ----
    churn_rate: float = 0.0
    recover_rate: float = 0.5
    straggle_rate: float = 0.0
    bw_walk: float = 0.0
    budget_bytes: float = 0.0
    # --- fault injection (compile-shaping; zero defaults = disabled) ------
    cluster_fail_rate: float = 0.0
    cluster_recover_rate: float = 0.25
    partition_start: int = -1
    partition_len: int = 0
    flap_rate: float = 0.0
    flap_len: int = 8
    crash_rate: float = 0.0
    rejoin_rate: float = 0.25
    warm_start: bool = False
    # --- B-connectivity watchdog (compile-shaping; 0 = disabled) ----------
    watchdog_window: int = 0
    watchdog_nprop: int = 0
    # --- engine ----------------------------------------------------------
    iters: int = 300
    mix_impl: str = "dense"  # see simulator.SIM_MIX_IMPLS
    shards: int = 1
    trace: str = "summary"  # service default: O(T m) cells batch freely
    eval_every: int = 10
    # --- request fan-out (traced; may vary within a batch group) ---------
    seeds: tuple[int, ...] = (0,)
    # sampler stream base: cell seed s stages batches with
    # FederatedBatches(seed=sample_seed + s), matching the historical
    # quickstart/sweep protocol (seed + 2)
    sample_seed: int = 2
    # queue policy (never compile-shaping): a request still waiting in the
    # service queue ``deadline_s`` seconds after submit is answered with an
    # error report instead of being launched.  0 = no deadline.
    deadline_s: float = 0.0

    def __post_init__(self):
        object.__setattr__(self, "seeds", tuple(int(s) for s in self.seeds))
        if not self.seeds:
            raise ValueError("seeds must name at least one seed")
        if self.deadline_s < 0:
            raise ValueError(f"deadline_s must be >= 0, got {self.deadline_s}")
        if self.topology not in TOPOLOGIES:
            raise ValueError(f"unknown topology {self.topology!r}; "
                             f"allowed: {TOPOLOGIES}")
        if self.time_varying not in TIME_VARYING:
            raise ValueError(f"unknown time_varying {self.time_varying!r}; "
                             f"allowed: {TIME_VARYING}")
        if self.partition not in PARTITIONS:
            raise ValueError(f"unknown partition {self.partition!r}; "
                             f"allowed: {PARTITIONS}")
        if self.n_train < 1 or self.n_test < 1:
            raise ValueError(f"n_train/n_test must be >= 1, got "
                             f"{self.n_train}/{self.n_test}")
        if self.eval_every < 1:
            raise ValueError(f"eval_every must be >= 1, got {self.eval_every}")
        # every SimConfig-level field (policy/model/optimizer/mix_impl/trace
        # registries, shards-vs-mix_impl, sharded-vs-trace, m/iters/batch
        # bounds) validates through the SimConfig constructor itself
        self.to_sim()

    def to_sim(self, *, seed: int | None = None,
               policy: str | None = None) -> SimConfig:
        """The ``SimConfig`` for one cell of this request."""
        return SimConfig(
            m=self.m, model=self.model, n_classes=self.n_classes,
            dim=self.dim, batch=self.batch, iters=self.iters,
            policy=self.policy if policy is None else policy,
            r=self.r, b_mean=self.b_mean, sigma_n=self.sigma_n,
            alpha0=self.alpha0, optimizer=self.optimizer,
            seed=self.seeds[0] if seed is None else int(seed),
            mix_impl=self.mix_impl, shards=self.shards, trace=self.trace,
            churn_rate=self.churn_rate, recover_rate=self.recover_rate,
            straggle_rate=self.straggle_rate, bw_walk=self.bw_walk,
            budget_bytes=self.budget_bytes,
            cluster_fail_rate=self.cluster_fail_rate,
            cluster_recover_rate=self.cluster_recover_rate,
            partition_start=self.partition_start,
            partition_len=self.partition_len,
            flap_rate=self.flap_rate, flap_len=self.flap_len,
            crash_rate=self.crash_rate, rejoin_rate=self.rejoin_rate,
            warm_start=self.warm_start,
            watchdog_window=self.watchdog_window,
            watchdog_nprop=self.watchdog_nprop)

    def signature(self) -> tuple:
        """Batch-compatibility key: every compile-shaping field.

        Two specs with equal signatures run on the same dataset, fabric,
        and compiled engine and may be served in one vmapped launch; specs
        with different signatures are never co-batched."""
        return tuple(getattr(self, f.name) for f in dataclasses.fields(self)
                     if f.name not in CELL_FIELDS)

    def batches(self, seed: int, ds: "Dataset") -> FederatedBatches:
        """The cell's deterministic sampler (shared by solo and batched
        serving paths, which is what makes them bit-identical)."""
        return FederatedBatches(ds.x, ds.y, ds.parts, self.batch,
                                seed=self.sample_seed + int(seed))


# ---------------------------------------------------------------------------
# data staging
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Dataset:
    x: np.ndarray
    y: np.ndarray
    parts: list
    x_test: np.ndarray
    y_test: np.ndarray


class SyntheticProvider:
    """Default data provider: the paper's synthetic image task.

    Caches staged datasets by value key so repeated requests share the SAME
    arrays -- the simulator's engine cache keys data by identity, so array
    reuse here is what turns "same scenario again" into an engine-cache hit
    instead of a recompile.  A custom provider is any callable
    ``provider(spec) -> Dataset`` honoring the same stability contract.
    """

    def __init__(self):
        self._cache: dict[tuple, Dataset] = {}

    @staticmethod
    def key(spec: ScenarioSpec) -> tuple:
        return (spec.m, spec.dim, spec.n_classes, spec.n_train, spec.n_test,
                spec.data_seed, spec.smooth, spec.partition,
                spec.labels_per_device, spec.dirichlet_alpha)

    def __call__(self, spec: ScenarioSpec) -> Dataset:
        if spec.model == "tiny_transformer":
            raise ValueError(
                "SyntheticProvider stages image data; model="
                "'tiny_transformer' needs token windows -- pass a custom "
                "provider (see examples/decentralized_transformer.py)")
        k = self.key(spec)
        ds = self._cache.get(k)
        if ds is None:
            x, y = image_dataset(spec.n_train, n_classes=spec.n_classes,
                                 dim=spec.dim, seed=spec.data_seed,
                                 smooth=spec.smooth)
            x_test, y_test = image_dataset(
                spec.n_test, n_classes=spec.n_classes, dim=spec.dim,
                seed=spec.data_seed + 1, smooth=spec.smooth)
            if spec.partition == "by_labels":
                parts = by_labels(y, spec.m, spec.labels_per_device)
            else:
                parts = dirichlet(y, spec.m, spec.dirichlet_alpha,
                                  seed=spec.data_seed)
            ds = Dataset(x, y, parts, x_test, y_test)
            self._cache[k] = ds
        return ds


_DEFAULT_PROVIDER = SyntheticProvider()


# Graph/eval staging caches, MODULE-level so the solo, sweep, and service
# paths all hand the engine cache the SAME objects (it keys eval fns by
# identity): a solo run of a scenario the service already compiled -- or
# vice versa -- is an engine-cache hit, not a recompile.  Graphs are cached
# by fabric value (rebuilding an RGG per request is wasted host work); eval
# fns by (model, id(dataset)), with the dataset kept alive in the value so
# a recycled id cannot alias a stale entry.
_GRAPH_CACHE: "OrderedDict[tuple, GraphProcess]" = OrderedDict()
_EVAL_CACHE: "OrderedDict[tuple, tuple[EvalFn, Dataset]]" = OrderedDict()
_STAGING_CACHE_SIZE = 32


class _Stager:
    """Binds a data provider to the shared graph/eval staging caches."""

    def __init__(self, provider: Callable[[ScenarioSpec], Dataset] | None):
        self.provider = provider or _DEFAULT_PROVIDER

    @staticmethod
    def graph(spec: ScenarioSpec) -> GraphProcess:
        k = (spec.m, spec.topology, spec.time_varying, spec.drop,
             spec.cycle_len, spec.graph_seed)
        g = _GRAPH_CACHE.get(k)
        if g is None:
            g = make_process(spec.m, spec.topology,
                             time_varying=spec.time_varying, drop=spec.drop,
                             cycle_len=spec.cycle_len, seed=spec.graph_seed)
            _GRAPH_CACHE[k] = g
            while len(_GRAPH_CACHE) > _STAGING_CACHE_SIZE:
                _GRAPH_CACHE.popitem(last=False)
        return g

    @staticmethod
    def eval_fn(spec: ScenarioSpec, ds: Dataset) -> EvalFn:
        k = (spec.model, spec.dim, spec.n_classes, id(ds))
        hit = _EVAL_CACHE.get(k)
        if hit is None:
            hit = (make_eval_fn(spec.to_sim(), ds.x_test, ds.y_test), ds)
            _EVAL_CACHE[k] = hit
            while len(_EVAL_CACHE) > _STAGING_CACHE_SIZE:
                _EVAL_CACHE.popitem(last=False)
        return hit[0]


# module-level stager for the one-shot entry points, so notebook loops of
# simulate()/sweep() calls reuse data/graph/eval staging (and therefore
# compiled engines) exactly like the resident service does
_SOLO_STAGER = _Stager(None)


def solo_run(spec: ScenarioSpec, *, seed: int | None = None,
             provider=None) -> SimResult:
    """One scenario, one seed, no batching: the definitional solo path
    (``repro.api.simulate``).  The batched service is bit-identical to
    this, per tests/test_service.py."""
    stager = _Stager(provider) if provider is not None else _SOLO_STAGER
    ds = stager.provider(spec)
    s = spec.seeds[0] if seed is None else int(seed)
    return simulator.run(
        spec.to_sim(seed=s), stager.graph(spec), spec.batches(s, ds),
        stager.eval_fn(spec, ds), eval_every=spec.eval_every)


def sweep_run(spec: ScenarioSpec, *, seeds: Sequence[int] | None = None,
              policies: Sequence[str] = triggers.POLICIES,
              provider=None) -> sweep_mod.SweepResult:
    """The seeds x policies grid for one scenario in a single compiled call
    (``repro.api.sweep``): ``spec.policy`` is ignored in favor of the
    ``policies`` axis."""
    stager = _Stager(provider) if provider is not None else _SOLO_STAGER
    ds = stager.provider(spec)
    return sweep_mod.run_sweep(
        spec.to_sim(), stager.graph(spec),
        lambda s: spec.batches(s, ds), stager.eval_fn(spec, ds),
        seeds=spec.seeds if seeds is None else seeds, policies=policies,
        eval_every=spec.eval_every)


# ---------------------------------------------------------------------------
# the service
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScenarioReport:
    """Per-request answer: results keyed by seed + latency/cache accounting.

    ``queue_wait_s`` is submit -> launch start; ``stage_s`` covers batch
    index staging for the whole launch; ``run_s`` the compiled execution +
    device transfer (both shared across the launch's requests).  A first
    execution at a given (signature, bucket) pays compile inside ``run_s``;
    ``program_cache_hit`` marks reuse."""

    request_id: int
    spec: ScenarioSpec
    launch_id: int
    results: dict[int, SimResult]  # seed -> trajectory
    tx: dict[int, accounting.TxSummary]  # seed -> transmission accounting
    queue_wait_s: float
    stage_s: float
    run_s: float
    launch_cells: int  # real cells co-batched in this launch
    engine_cache_hit: bool
    program_cache_hit: bool
    # non-None when this request's round failed: the error message, with
    # ``results``/``tx`` empty.  Other rounds keep draining (a poisoned spec
    # must not strand the rest of the queue).
    error: str | None = None
    # seeds whose trajectory diverged (non-finite loss / consensus error):
    # their cells are withheld from ``results`` so a NaN can never be read
    # as an answer, while the finite co-batched cells come back untouched
    quarantined: tuple[int, ...] = ()
    # poll rounds this request was relaunched after a contained failure
    retries: int = 0

    @property
    def ok(self) -> bool:
        return self.error is None

    def result(self, seed: int | None = None) -> SimResult:
        if self.error is not None:
            raise RuntimeError(
                f"request {self.request_id} failed: {self.error}")
        s = self.spec.seeds[0] if seed is None else seed
        if s in self.quarantined:
            raise RuntimeError(
                f"request {self.request_id} seed {s} was quarantined: "
                "trajectory diverged (non-finite loss/consensus_err)")
        return self.results[s]

    def timing_dict(self) -> dict:
        return {"request_id": self.request_id, "launch_id": self.launch_id,
                "queue_wait_s": self.queue_wait_s, "stage_s": self.stage_s,
                "run_s": self.run_s, "launch_cells": self.launch_cells,
                "cells": len(self.results),
                "engine_cache_hit": self.engine_cache_hit,
                "program_cache_hit": self.program_cache_hit}


@dataclasses.dataclass
class ServiceStats:
    requests: int = 0
    cells: int = 0
    launches: int = 0
    program_hits: int = 0
    program_misses: int = 0
    padded_cells: int = 0  # bucket-padding overhead cells executed
    failures: int = 0  # requests answered with error-tagged reports
    retries: int = 0  # failed requests re-queued for another round
    deadline_expired: int = 0  # requests expired in queue, never launched
    quarantined: int = 0  # diverged (non-finite) cells withheld
    engine: simulator.EngineCacheStats = dataclasses.field(
        default_factory=simulator.EngineCacheStats)

    def as_dict(self) -> dict:
        return {"requests": self.requests, "cells": self.cells,
                "launches": self.launches, "program_hits": self.program_hits,
                "program_misses": self.program_misses,
                "padded_cells": self.padded_cells,
                "failures": self.failures, "retries": self.retries,
                "deadline_expired": self.deadline_expired,
                "quarantined": self.quarantined,
                "engine_cache": self.engine.as_dict()}


@dataclasses.dataclass
class _Pending:
    rid: int
    spec: ScenarioSpec
    sig: tuple
    t_submit: float
    attempts: int = 0  # launch attempts already consumed (for retry caps)


def _bucket(n: int) -> int:
    """Next power-of-two cell count: padding launches up to a bucket keeps
    the program shape stable across rounds with different request counts,
    so jit's compile cache hits instead of re-tracing per count."""
    b = 1
    while b < n:
        b *= 2
    return b


class ScenarioService:
    """Resident continuous-batching scenario server.

    ``submit`` enqueues; ``poll`` serves one round: it takes the oldest
    request's signature, gathers every queued compatible request up to
    ``max_cells`` cells (FIFO within the signature), and launches them as
    one vmapped program.  ``serve`` is the synchronous driver: submit a
    batch, poll until drained.  A signature whose queue exceeds
    ``max_cells`` simply drains over multiple rounds -- later rounds hit
    the engine + program caches, which is the continuous-batching story:
    compile once, stream cells through.

    ``mix_impl="sharded"`` requests are accepted but execute their cells
    serially, as ``fl.sweep`` does for sharded grids (vmap over the
    shard_map engine composes, ``tests/sharded_worker.py vmap``, but a
    batched grid of fleets that need sharding would not fit); they still
    share one compiled
    engine via the simulator cache.

    Hardening (DESIGN.md "Fault injection & resilience"): a round that
    fails is retried up to ``max_retries`` times per request with
    exponential backoff before the error report goes out; a request whose
    spec carries ``deadline_s`` and is still queued past it is expired
    without launching; cells whose trajectory diverged to NaN/Inf are
    quarantined out of the report without touching their co-batched
    neighbors.
    """

    def __init__(self, provider=None, *, max_cells: int = 16,
                 max_retries: int = 1, retry_backoff_s: float = 0.05):
        if max_cells < 1:
            raise ValueError(f"max_cells must be >= 1, got {max_cells}")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        if retry_backoff_s < 0:
            raise ValueError(
                f"retry_backoff_s must be >= 0, got {retry_backoff_s}")
        self._stager = _Stager(provider)
        self.max_cells = max_cells
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self._queue: deque[_Pending] = deque()
        self._next_id = 0
        # vmapped-grid cache per engine instance (engines themselves live in
        # the simulator's value-keyed LRU); OrderedDict for LRU eviction
        self._grids: "OrderedDict[int, tuple]" = OrderedDict()
        self._grids_size = 16
        self._seen_programs: set[tuple] = set()
        self._stats = ServiceStats()

    # ------------------------------------------------------------- queue --
    def submit(self, spec: ScenarioSpec) -> int:
        if not isinstance(spec, ScenarioSpec):
            raise TypeError(f"submit takes a ScenarioSpec, got "
                            f"{type(spec).__name__}")
        rid = self._next_id
        self._next_id += 1
        self._queue.append(_Pending(rid, spec, spec.signature(),
                                    time.perf_counter()))
        self._stats.requests += 1
        return rid

    def pending(self) -> int:
        return len(self._queue)

    def stats(self) -> ServiceStats:
        return dataclasses.replace(self._stats,
                                   engine=simulator.engine_cache_stats())

    # ------------------------------------------------------------- rounds --
    def _expire(self) -> list[ScenarioReport]:
        """Sweeps the queue for requests past their ``deadline_s``: they are
        answered with error reports instead of being launched (a stale
        what-if is worth less than the round it would occupy)."""
        t_now = time.perf_counter()
        expired = [p for p in self._queue
                   if p.spec.deadline_s > 0
                   and t_now - p.t_submit > p.spec.deadline_s]
        reports: list[ScenarioReport] = []
        for p in expired:
            self._queue.remove(p)
            self._stats.deadline_expired += 1
            reports.append(ScenarioReport(
                request_id=p.rid, spec=p.spec, launch_id=-1, results={},
                tx={}, queue_wait_s=t_now - p.t_submit, stage_s=0.0,
                run_s=0.0, launch_cells=0, engine_cache_hit=False,
                program_cache_hit=False, retries=p.attempts,
                error=(f"DeadlineExceeded: queued "
                       f"{t_now - p.t_submit:.3f}s > deadline_s="
                       f"{p.spec.deadline_s}")))
        return reports

    def poll(self) -> list[ScenarioReport]:
        """Serves one batch round; [] when the queue is empty.

        A staging/engine failure is contained to the round: the failed
        requests are re-queued (up to ``max_retries`` attempts each, with
        ``retry_backoff_s * 2**attempt`` backoff) or come back as
        error-tagged reports, and the rest of the queue keeps draining on
        later polls -- one poisoned spec must not strand every request
        behind it in ``serve``."""
        reports = self._expire()
        if not self._queue:
            return reports
        sig = self._queue[0].sig
        group: list[_Pending] = []
        budget = self.max_cells
        for p in list(self._queue):
            n = len(p.spec.seeds)
            if p.sig == sig and (n <= budget or not group):
                group.append(p)
                budget -= n
                self._queue.remove(p)
        try:
            return reports + self._launch(group)
        except Exception as e:  # noqa: BLE001 -- contain any round failure
            t_now = time.perf_counter()
            backoff = 0.0
            for p in group:
                if p.attempts < self.max_retries:
                    p.attempts += 1
                    self._stats.retries += 1
                    backoff = max(
                        backoff,
                        self.retry_backoff_s * 2 ** (p.attempts - 1))
                    self._queue.append(p)  # back of the queue: FIFO fairness
                else:
                    self._stats.failures += 1
                    reports.append(ScenarioReport(
                        request_id=p.rid, spec=p.spec, launch_id=-1,
                        results={}, tx={}, queue_wait_s=t_now - p.t_submit,
                        stage_s=0.0, run_s=0.0, launch_cells=0,
                        engine_cache_hit=False, program_cache_hit=False,
                        retries=p.attempts,
                        error=f"{type(e).__name__}: {e}"))
            if backoff:
                time.sleep(backoff)
            return reports

    def serve(self, specs: Sequence[ScenarioSpec] = ()) -> list[ScenarioReport]:
        """Submit ``specs``, drain the queue, return reports by request id."""
        for spec in specs:
            self.submit(spec)
        reports: list[ScenarioReport] = []
        while self._queue:
            reports.extend(self.poll())
        return sorted(reports, key=lambda r: r.request_id)

    # ------------------------------------------------------------- launch --
    def _grid_for(self, eng) -> Callable:
        k = id(eng)
        hit = self._grids.get(k)
        if hit is None:
            hit = (jax.jit(jax.vmap(eng)), eng)
            self._grids[k] = hit
            while len(self._grids) > self._grids_size:
                self._grids.popitem(last=False)
        else:
            self._grids.move_to_end(k)
        return hit[0]

    def _launch(self, group: list[_Pending]) -> list[ScenarioReport]:
        spec0 = group[0].spec
        t_start = time.perf_counter()
        launch_id = self._stats.launches
        self._stats.launches += 1
        cells = [(p, s) for p in group for s in p.spec.seeds]
        # host spans on the profiler's clock; one launch's spans share its id
        span = functools.partial(jax.profiler.TraceAnnotation,
                                 launch_id=launch_id, cells=len(cells))

        with span("service.stage"):
            ds = self._stager.provider(spec0)
            graph = self._stager.graph(spec0)
            eval_fn = self._stager.eval_fn(spec0, ds)
            self._stats.cells += len(cells)
            staged = (None if spec0.mix_impl == "sharded" else
                      self._stage_grid(spec0, cells, ds, graph, eval_fn))
        if staged is None:
            return self._launch_serial(group, cells, ds, graph, eval_fn,
                                       t_start, launch_id, span)
        eng, model_dim, engine_hit, pol, seeds, idx = staged
        t_staged = time.perf_counter()

        with span("service.launch"):
            prog_key = (group[0].sig, len(pol))
            program_hit = prog_key in self._seen_programs
            self._seen_programs.add(prog_key)
            self._stats.program_hits += int(program_hit)
            self._stats.program_misses += int(not program_hit)
            out = self._grid_for(eng)(pol, seeds, idx)
        with span("service.fetch"):
            host = jax.device_get(out)
        t_done = time.perf_counter()

        with span("service.report"):
            results = [simulator._result_from_device(
                jax.tree.map(lambda a: a[i], host), model_dim, spec0.trace)
                for i in range(len(cells))]
            return self._reports(group, cells, results, t_start=t_start,
                                 stage_s=t_staged - t_start,
                                 run_s=t_done - t_staged, launch_id=launch_id,
                                 engine_hit=engine_hit,
                                 program_hit=program_hit)

    def _stage_grid(self, spec0, cells, ds, graph, eval_fn):
        """The launch's engine (cache hit or not) and its (policy, seed,
        index) inputs, padded to the launch's bucket of cells."""
        before = simulator.engine_cache_stats()
        eng, model_dim = simulator._cached_engine(
            spec0.to_sim(), graph, T=spec0.iters,
            eval_every=spec0.eval_every, x=ds.x, y=ds.y, eval_fn=eval_fn)
        engine_hit = simulator.engine_cache_stats().hits > before.hits

        pol = np.asarray([triggers.policy_index(p.spec.policy)
                          for p, _ in cells], np.int32)
        seeds = np.asarray([s for _, s in cells], np.int32)
        idx = np.stack([p.spec.batches(s, ds).stage(p.spec.iters)
                        for p, s in cells])
        n = len(cells)
        b = min(_bucket(n), max(self.max_cells, n))
        if b > n:  # pad with copies of cell 0; padded outputs are dropped
            pad = b - n
            self._stats.padded_cells += pad
            rep = lambda a: np.concatenate([a, np.repeat(a[:1], pad, 0)])
            pol, seeds, idx = rep(pol), rep(seeds), rep(idx)
        return eng, model_dim, engine_hit, pol, seeds, idx

    def _launch_serial(self, group, cells, ds, graph, eval_fn, t_start,
                       launch_id, span) -> list[ScenarioReport]:
        before = simulator.engine_cache_stats()
        with span("service.launch"):  # each run adds its own sim.* spans
            results = [simulator.run(
                p.spec.to_sim(seed=s), graph, p.spec.batches(s, ds),
                eval_fn, eval_every=p.spec.eval_every) for p, s in cells]
        after = simulator.engine_cache_stats()
        t_done = time.perf_counter()
        with span("service.report"):
            return self._reports(group, cells, results, t_start=t_start,
                                 stage_s=0.0, run_s=t_done - t_start,
                                 launch_id=launch_id,
                                 engine_hit=after.hits > before.hits,
                                 program_hit=after.misses == before.misses)

    @staticmethod
    def _diverged(res: SimResult) -> bool:
        """A cell whose loss or consensus error ever left the finite range
        is quarantined: NaN/Inf trajectories must never be read as answers."""
        return not (np.isfinite(res.loss).all()
                    and np.isfinite(res.consensus_err).all())

    def _reports(self, group, cells, results, *, t_start, stage_s, run_s,
                 launch_id, engine_hit, program_hit) -> list[ScenarioReport]:
        per_req: dict[int, dict[int, SimResult]] = {p.rid: {} for p in group}
        bad: dict[int, list[int]] = {p.rid: [] for p in group}
        for (p, s), res in zip(cells, results):
            if self._diverged(res):
                bad[p.rid].append(s)
                self._stats.quarantined += 1
            else:
                per_req[p.rid][s] = res
        return [ScenarioReport(
            request_id=p.rid, spec=p.spec, launch_id=launch_id,
            results=per_req[p.rid],
            tx={s: accounting.tx_summary_from_result(r)
                for s, r in per_req[p.rid].items()},
            queue_wait_s=t_start - p.t_submit, stage_s=stage_s, run_s=run_s,
            launch_cells=len(cells), engine_cache_hit=engine_hit,
            program_cache_hit=program_hit, retries=p.attempts,
            quarantined=tuple(bad[p.rid])) for p in group]
