"""Decentralized FL simulator (the paper's Sec. IV experiment harness).

Runs EF-HC (or a baseline trigger policy) for m devices with vmap over the
device axis, collecting the paper's metrics per iteration: per-device loss,
average accuracy, transmission time, utilization, trigger trace, and the
information-flow edges for B-connectivity checks.

Two engines produce the same ``SimResult`` (see DESIGN.md "Scan engine"):

* ``engine="scan"`` (default) - device-resident: batches are pre-staged as
  index arrays (``FederatedBatches.stage``), the T iterations run as a
  chunked ``jax.lax.scan`` (chunk = ``eval_every``) with evaluation folded
  into the compiled program, and every T x m metric is accumulated in scan
  ys.  One host<->device sync per run (the final ``device_get``) instead of
  ~8 per iteration.  ``make_engine`` exposes the underlying pure function,
  which ``repro.fl.sweep`` vmaps over seeds and trigger policies.
* ``engine="python"`` - the legacy per-step host loop, kept as the reference
  for the scan-parity test and for custom host-side eval callables.

Models: ``svm`` - linear multi-class SVM with multi-margin loss (paper's
convex model); ``mlp`` - small non-convex classifier standing in for LeNet5
(Appendix J) without conv dependencies.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import efhc, triggers
from repro.core import faults as faults_mod
from repro.core import flow as flow_mod
from repro.core import resources as resources_mod
from repro.core.topology import GraphProcess
from repro.data.loader import FederatedBatches
from repro.fl import modelspec as modelspec_mod
from repro.fl import trace as trace_mod
# canonical model implementations live in repro.fl.modelspec; re-exported
# here because the simulator was their historical home
from repro.fl.modelspec import (ModelSpec, init_mlp, init_svm, make_model_spec,
                                mlp_logits, multi_margin_loss, svm_logits,
                                xent_loss)
from repro.optim.optimizers import OPT_NAMES, init_opt
from repro.optim.schedules import paper_diminishing


def model_fns(sim: "SimConfig"):
    """Legacy (init_fn, logits_fn, loss_base) triple for the paper models.

    Subsumed by ``model_spec`` / ``repro.fl.modelspec.ModelSpec``, which
    also covers the real multi-layer networks; kept because the
    ``init_fn(key, dim, n_classes)`` calling convention is part of old
    notebooks' muscle memory."""
    if sim.model == "svm":
        return init_svm, svm_logits, multi_margin_loss
    if sim.model == "mlp":
        return init_mlp, mlp_logits, xent_loss
    raise ValueError(
        f"model_fns only covers the paper models ('svm'/'mlp'); use "
        f"model_spec(sim) for model={sim.model!r}")


def model_spec(sim: "SimConfig") -> ModelSpec:
    """The ``ModelSpec`` for this config (DESIGN.md "Model plumbing")."""
    return make_model_spec(sim.model, dim=sim.dim, n_classes=sim.n_classes)


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

# every mix_impl a SimConfig may name: the efhc-level impls plus "sharded",
# which routes to the shard_map fleet engine (repro.fl.sharded)
SIM_MIX_IMPLS: tuple[str, ...] = efhc.MIX_IMPLS + ("sharded",)


@dataclasses.dataclass
class SimConfig:
    m: int = 10
    # any repro.fl.modelspec registry name: svm | mlp | cnn | mlp_blocks |
    # tiny_transformer (the last takes (batch, seq) int32 token windows)
    model: str = "svm"
    n_classes: int = 10
    dim: int = 784
    batch: int = 16
    iters: int = 300
    policy: str = "efhc"  # efhc | zero | global | gossip
    r: float = 50.0  # threshold scale (paper: b_M * 1e-2)
    b_mean: float = 5000.0
    sigma_n: float = 0.9
    alpha0: float = 0.1
    # Event-4 local update rule: sgd (the paper's; bit-identical to the
    # historical inline expression) | momentum | adam.  Optimizer state
    # rides EFHCState.opt_state through the scan carry.
    optimizer: str = "sgd"
    seed: int = 0
    # dense | delta | pallas (fused kernels) | sparse | sparse_delta |
    # sparse_pallas (neighbor-list aggregation, the m >= 4096 path --
    # DESIGN.md "Sparse mixing"); see efhc.MIX_IMPLS.  "sharded" routes to
    # the shard_map fleet engine (repro.fl.sharded): the ELL mix partitioned
    # over `shards` devices with halo exchange, the m >= 10^5 path
    mix_impl: str = "dense"
    # fleet shards for mix_impl="sharded" (1-D "fl" mesh; needs that many
    # jax devices and m % shards == 0); ignored by every other impl
    shards: int = 1
    # link-matrix trajectory storage: "full" (T, m, m) bool, "packed"
    # bit-packed uint32 words (8x smaller, lossless), "summary" per-device
    # counts only (O(T m); required for m >~ 512 horizons) -- DESIGN.md
    # "Trace modes"
    trace: str = "full"
    # resource dynamics (DESIGN.md "Resource dynamics"): all-zero defaults
    # keep the engines on the structurally identical pre-resource path
    # (golden trajectories stay bit-exact); any nonzero knob enables the
    # per-device resource process inside the scan
    churn_rate: float = 0.0  # P(up device goes down) per iteration
    recover_rate: float = 0.5  # P(down device comes back) per iteration
    straggle_rate: float = 0.0  # P(device delays its Event-4 update)
    bw_walk: float = 0.0  # log-space bandwidth random-walk std per iter
    budget_bytes: float = 0.0  # per-device broadcast budget; 0 = unlimited
    # correlated fault injection (DESIGN.md "Fault injection & resilience"):
    # same contract -- all-default knobs keep the engines on the
    # structurally identical pre-fault path
    cluster_fail_rate: float = 0.0  # P(an up cluster goes down) per iter
    cluster_recover_rate: float = 0.25  # P(a down cluster recovers)
    partition_start: int = -1  # first iter of the scripted bridge partition
    partition_len: int = 0  # partition window length; 0 disables
    flap_rate: float = 0.0  # fraction of base edges marked flapping
    flap_len: int = 8  # flap square-wave half-period (iterations)
    crash_rate: float = 0.0  # P(device crashes) per iteration
    rejoin_rate: float = 0.25  # P(crashed device rejoins) per iteration
    warm_start: bool = False  # rejoin from live-neighbor average, not stale theta
    # in-scan B-connectivity watchdog: sliding union window to certify
    # (0 = off); propagation rounds per iteration (0 = auto)
    watchdog_window: int = 0
    watchdog_nprop: int = 0

    def __post_init__(self):
        """Fail-fast field validation (DESIGN.md "Scenario service").

        Every registry-valued field is checked against its registry here,
        at construction, with the allowed values named -- instead of
        surfacing later as a KeyError in ``init_opt``, a ``lax.switch``
        branch-count blowup, or a shape error three engines deep.  Illegal
        combinations (``shards`` without the sharded engine, a sharded run
        asking for link-matrix traces) are rejected the same way."""
        if self.m < 1:
            raise ValueError(f"m must be >= 1, got {self.m}")
        if self.iters < 1:
            raise ValueError(f"iters must be >= 1, got {self.iters}")
        if self.batch < 1:
            raise ValueError(f"batch must be >= 1, got {self.batch}")
        if self.policy not in triggers.POLICIES:
            raise ValueError(f"unknown policy {self.policy!r}; "
                             f"allowed: {triggers.POLICIES}")
        if self.model not in modelspec_mod.MODEL_NAMES:
            raise ValueError(f"unknown model {self.model!r}; "
                             f"allowed: {modelspec_mod.MODEL_NAMES}")
        if self.optimizer not in OPT_NAMES:
            raise ValueError(f"unknown optimizer {self.optimizer!r}; "
                             f"allowed: {OPT_NAMES}")
        if self.mix_impl not in SIM_MIX_IMPLS:
            raise ValueError(f"unknown mix_impl {self.mix_impl!r}; "
                             f"allowed: {SIM_MIX_IMPLS}")
        trace_mod.check_trace_mode(self.trace)
        if self.shards < 1:
            raise ValueError(f"shards must be >= 1, got {self.shards}")
        if self.shards > 1 and self.mix_impl != "sharded":
            raise ValueError(
                f"shards={self.shards} requires mix_impl='sharded' "
                f"(got mix_impl={self.mix_impl!r}); every other impl runs "
                f"single-device")
        if self.mix_impl == "sharded" and self.trace != "summary":
            raise ValueError(
                f"mix_impl='sharded' keeps only summary traces (per-device "
                f"counts); got trace={self.trace!r} -- link matrices would "
                f"densify (T, m, m) at fleet scale")
        triggers.check_sigma_n(self.sigma_n)
        self.resources()  # ResourceConfig.__post_init__ validates the knobs
        self.faults()  # FaultConfig.__post_init__ validates the knobs
        self.watchdog()  # WatchdogConfig.__post_init__ validates the knobs

    def resources(self) -> resources_mod.ResourceConfig | None:
        """The run's ``ResourceConfig``, or None when every knob is at its
        disabled default (the engines branch on this at Python level).

        ``ResourceConfig.seed`` stays 0: the resource stream already derives
        from the engine's TRACED root key (``PRNGKey(seed)``), so per-run
        variation rides the run seed -- and a batched service cell realizes
        the same stream as its solo counterpart, which a static config-seed
        fold (baked into the shared compiled engine) would break."""
        rcfg = resources_mod.ResourceConfig(
            churn_rate=self.churn_rate, recover_rate=self.recover_rate,
            straggle_rate=self.straggle_rate, bw_walk=self.bw_walk,
            budget_bytes=self.budget_bytes)
        return rcfg if rcfg.enabled else None

    def faults(self) -> faults_mod.FaultConfig | None:
        """The run's ``FaultConfig``, or None when disabled.  Like the
        resource stream, the fault stream derives from the TRACED root key
        (``FaultConfig.seed`` stays 0 so service-batched cells match solo
        runs); the staging-time flap assignment is a scenario property."""
        fcfg = faults_mod.FaultConfig(
            cluster_fail_rate=self.cluster_fail_rate,
            cluster_recover_rate=self.cluster_recover_rate,
            partition_start=self.partition_start,
            partition_len=self.partition_len,
            flap_rate=self.flap_rate, flap_len=self.flap_len,
            crash_rate=self.crash_rate, rejoin_rate=self.rejoin_rate,
            warm_start=self.warm_start)
        return fcfg if fcfg.enabled else None

    def watchdog(self) -> flow_mod.WatchdogConfig | None:
        """The run's ``WatchdogConfig``, or None when ``watchdog_window``
        is 0 (the engines then stay structurally watchdog-free)."""
        wcfg = flow_mod.WatchdogConfig(window=self.watchdog_window,
                                       n_prop=self.watchdog_nprop)
        return wcfg if wcfg.enabled else None


@dataclasses.dataclass
class SimResult:
    """Host-side trajectory contract (stable across engines and trace modes).

    The link matrices ``comm``/``adj`` are *accessors*: storage follows
    ``trace`` -- dense bool (``full``), bit-packed uint32 (``packed``,
    unpacked losslessly on access), or absent (``summary``, access raises).
    The per-device row sums ``comm_count``/``deg`` are recorded in every
    mode and are what the tx-time / utilization / B-connectivity-count
    metrics consume."""

    loss: np.ndarray  # (T, m)
    acc: np.ndarray  # (T,)
    tx_time: np.ndarray  # (T,)
    util: np.ndarray  # (T,)
    v: np.ndarray  # (T, m)
    comm_count: np.ndarray  # (T, m) int32: info-flow links used per device
    deg: np.ndarray  # (T, m) int32: physical degree per device
    consensus_err: np.ndarray  # (T,)
    model_dim: int
    bandwidths: np.ndarray
    trace: str = "full"
    _comm: np.ndarray | None = None  # (T,m,m) bool | (T,m,W) uint32 | None
    _adj: np.ndarray | None = None
    # resource-dynamics channels (trace.RESOURCE_CHANNELS): (T,) int32
    # per-iteration counts of down / budget-exhausted devices; all-zero for
    # runs without a resource process (None only from pre-resource pickles)
    down_count: np.ndarray | None = None
    exhausted_count: np.ndarray | None = None
    # fault-injection channels (trace.FAULT_CHANNELS): (T,) int32 devices
    # silenced by crash/cluster outage, and worst rejoin staleness in flight
    fault_down_count: np.ndarray | None = None
    stale_max: np.ndarray | None = None
    # watchdog channels (trace.WATCHDOG_CHANNELS): (T,) bool / int32 --
    # all-True / all-zero for runs without a watchdog
    window_connected: np.ndarray | None = None
    window_needed: np.ndarray | None = None

    @property
    def m(self) -> int:
        return int(self.bandwidths.shape[-1])

    @property
    def comm(self) -> np.ndarray:  # (T, m, m) bool
        return trace_mod.stored_links(self._comm, self.trace, self.m, "comm")

    @property
    def adj(self) -> np.ndarray:  # (T, m, m) bool
        return trace_mod.stored_links(self._adj, self.trace, self.m, "adj")

    @property
    def cum_tx_time(self) -> np.ndarray:
        return np.cumsum(self.tx_time)


class EvalFn:
    """Accuracy evaluation with both host and device entry points.

    ``device(w_stack)`` is a pure jittable function (mean test accuracy over
    devices) that the scan engine folds into its compiled program;
    ``__call__`` wraps it for the legacy host loop.
    """

    def __init__(self, logits_fn, x_test: np.ndarray, y_test: np.ndarray):
        self._logits_fn = logits_fn
        self.x_test = jnp.asarray(x_test)
        self.y_test = jnp.asarray(y_test)
        self._jit = jax.jit(self.device)

    def device(self, w_stack) -> jax.Array:
        def one(w):
            return (self._logits_fn(w, self.x_test).argmax(-1) == self.y_test).mean()

        return jax.vmap(one)(w_stack).mean()

    def __call__(self, w_stack) -> float:
        return float(self._jit(jax.tree.map(jnp.asarray, w_stack)))


def make_eval_fn(sim: SimConfig, x_test: np.ndarray, y_test: np.ndarray) -> EvalFn:
    return EvalFn(model_spec(sim).eval_logits, x_test, y_test)


# legacy alias: ModelSpec.grad_fn is built by the same factory
_grad_fn = modelspec_mod.make_grad_fn


def _efhc_cfg(sim: SimConfig) -> efhc.EFHCConfig:
    return efhc.EFHCConfig(
        trigger=triggers.TriggerConfig(policy=sim.policy, r=sim.r, b_mean=sim.b_mean),
        gamma=None,
        mix_impl=sim.mix_impl,
        resources=sim.resources(),
        faults=sim.faults(),
        watchdog=sim.watchdog(),
    )


def _model_dim(sim: SimConfig) -> int:
    """Exact parameter count = flat-view width D (the bytes a broadcast
    actually ships).  Subsumed by ``model_spec(sim).flat_dim``."""
    return model_spec(sim).flat_dim


class _EngineCore:
    """Shared staging + scan closures behind both engine entry points.

    ``make_engine`` runs ``init`` + one ``span`` over the whole horizon;
    ``run_checkpointed`` runs the SAME ``span`` over consecutive segments,
    persisting the carry between them.  Because the two paths trace the
    verbatim-identical chunk body, a resumed run replays the uninterrupted
    program bit for bit (pinned by tests/test_checkpoint_resume.py)."""

    def __init__(self, sim: SimConfig, graph: GraphProcess, *,
                 eval_every: int, x, y, eval_fn):
        self.E = max(1, int(eval_every))
        self.m = sim.m
        self.sim = sim
        self.graph = graph
        self.trace = trace_mod.check_trace_mode(sim.trace)
        self.spec = model_spec(sim)
        self.opt = init_opt(sim.optimizer)
        self.cfg = _efhc_cfg(sim)
        self.sched = paper_diminishing(sim.alpha0, gamma=1.0, theta=0.5)
        self.model_dim = self.spec.flat_dim
        self.x_all, self.y_all = jnp.asarray(x), jnp.asarray(y)
        self.eval_dev = eval_fn.device if isinstance(eval_fn, EvalFn) else eval_fn
        # sparse impls carry Event-1 state as the ELL slot mask of G^(k-1);
        # the watchdog needs the neighbor list under EVERY impl (dense comm
        # matrices are gathered into its slot layout)
        self.sparse = self.cfg.mix_impl in efhc.SPARSE_MIX_IMPLS
        self.nl = (graph.neighbors()
                   if self.sparse or self.cfg.watchdog is not None else None)
        self.rcfg = self.cfg.resources
        self.fcfg = self.cfg.faults
        self.wcfg = self.cfg.watchdog
        if self.fcfg is not None:
            self.fab = faults_mod.fault_fabric(graph, self.fcfg)
            if self.sparse:
                self.ftabs = faults_mod.edge_tables_rows(
                    self.fab, graph.edges, self.nl.idx, self.nl.mask)
            else:
                self.ftabs = faults_mod.edge_tables_dense(
                    self.fab, graph.edges)
        else:
            self.fab, self.ftabs = None, None

    def init(self, seed) -> tuple[efhc.EFHCState, jax.Array]:
        """Initial carry + bandwidths for a run seed (pure, jit-able)."""
        with jax.named_scope("efhc.init"):
            sim, graph = self.sim, self.graph
            key = jax.random.PRNGKey(seed)
            k_bw, k_init, k_state = jax.random.split(key, 3)
            bw = triggers.sample_bandwidths(k_bw, self.m, sim.b_mean, sim.sigma_n)
            w0 = self.spec.init_stack(k_init, self.m)
            adj0 = (graph.adjacency_ell(0, self.nl) if self.sparse
                    else graph.adjacency(0))
            res0 = (resources_mod.init_state(
                        self.rcfg, bw, resources_mod.resource_key(key, self.rcfg))
                    if self.rcfg is not None else None)
            f0 = (faults_mod.init_state(
                      self.fcfg, self.fab, faults_mod.fault_key(key, self.fcfg))
                  if self.fcfg is not None else None)
            wd0 = (flow_mod.watchdog_init(self.m, self.nl.idx.shape[1])
                   if self.wcfg is not None else None)
            state = efhc.init_state(w0, bw, adj0, k_state,
                                    opt_state=self.opt.init(w0), resources=res0,
                                    faults=f0, watchdog=wd0)
            return state, bw

    def trace_ys(self, aux: efhc.StepAux) -> dict:
        """Per-iteration scan ys: the (m, m) float P matrix is never
        carried (SimResult doesn't expose it) and the bool link matrices
        are stored per ``sim.trace`` -- dense, bit-packed uint32 words,
        or row-sum summaries only (DESIGN.md "Trace modes").  The row
        sums come from StepAux directly, so under trace="summary" the
        ys never touch aux.comm/aux.adj at all -- which is what lets
        the sparse mix impls dead-code-eliminate the dense scatters."""
        with jax.named_scope("efhc.ys"):
            ys = {"loss": aux.loss, "tx_time": aux.tx_time, "util": aux.util,
                  "v": aux.v, "consensus_err": aux.consensus_err,
                  "comm_count": aux.comm_count, "deg": aux.deg,
                  "down_count": aux.down_count,
                  "exhausted_count": aux.exhausted_count,
                  "fault_down_count": aux.fault_down_count,
                  "stale_max": aux.stale_max,
                  "window_connected": aux.window_connected,
                  "window_needed": aux.window_needed}
            if self.trace == "full":
                ys["comm"], ys["adj"] = aux.comm, aux.adj
            elif self.trace == "packed":
                ys["comm"] = trace_mod.pack_links(aux.comm)
                ys["adj"] = trace_mod.pack_links(aux.adj)
            return ys

    def span(self, policy_idx, state: efhc.EFHCState, idx, alphas, *,
             final: bool):
        """Scans ``idx.shape[0]`` iterations from ``state`` (chunked by
        ``E``, on-device eval at the chunk firsts).  ``final`` adds the
        legacy k == T-1 eval overwrite -- True for a whole-horizon run and
        the last checkpoint segment, False for interior segments."""
        policy_idx = jnp.asarray(policy_idx, jnp.int32)
        T_span, E = idx.shape[0], self.E

        def one_step(st, per):
            ix, alpha = per  # ix: (m, batch) dataset rows for this iteration
            with jax.named_scope("efhc.event4"):
                batch = (self.x_all[ix], self.y_all[ix])
            st, aux = efhc.step(self.cfg, self.graph, st,
                                grad_fn=self.spec.grad_fn, batch=batch,
                                alpha_k=alpha, model_dim=self.model_dim,
                                policy_idx=policy_idx, nl=self.nl,
                                opt_update=self.opt.update, ftabs=self.ftabs)
            return st, self.trace_ys(aux)

        def eval_acc(st):
            if self.eval_dev is None:
                return jnp.asarray(0.0, jnp.float32)
            with jax.named_scope("efhc.eval"):
                return self.eval_dev(st.w).astype(jnp.float32)

        def chunk_body(st, chunk):
            # eval after the chunk's first step = iterations 0, E, 2E, ...
            # (the legacy loop's schedule), then scan the remaining E-1 steps
            st, aux0 = one_step(st, jax.tree.map(lambda a: a[0], chunk))
            acc = eval_acc(st)
            st, auxr = jax.lax.scan(one_step, st, jax.tree.map(lambda a: a[1:], chunk))
            aux = jax.tree.map(lambda a, b: jnp.concatenate([a[None], b], 0), aux0, auxr)
            return st, (aux, acc)

        per = (idx, alphas)
        n_full, rem = divmod(T_span, E)
        head = jax.tree.map(
            lambda a: a[: n_full * E].reshape((n_full, E) + a.shape[1:]), per)
        state, (aux_h, accs) = jax.lax.scan(chunk_body, state, head)
        aux = jax.tree.map(lambda a: a.reshape((n_full * E,) + a.shape[2:]), aux_h)
        acc_t = jnp.repeat(accs, E, total_repeat_length=n_full * E)
        if rem:
            tail = jax.tree.map(lambda a: a[n_full * E:], per)
            state, (aux_r, acc_r) = chunk_body(state, tail)
            aux = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), aux, aux_r)
            acc_t = jnp.concatenate([acc_t, jnp.full((rem,), acc_r)])
        if final:
            acc_t = acc_t.at[T_span - 1].set(eval_acc(state))  # legacy's k == T-1 eval
        return state, {**aux, "acc": acc_t}


def make_engine(
    sim: SimConfig,
    graph: GraphProcess,
    *,
    T: int,
    eval_every: int = 10,
    x: np.ndarray,
    y: np.ndarray,
    eval_fn: EvalFn | None = None,
):
    """Builds the device-resident simulation engine: a pure function

        engine(policy_idx, seed, idx) -> dict of full trajectories

    with ``policy_idx`` a (traced) index into ``triggers.POLICIES``, ``seed``
    a (traced) int, and ``idx`` the (T, m, batch) staged dataset indices from
    ``FederatedBatches.stage``.  The T iterations run as a chunked
    ``lax.scan`` (chunk = ``eval_every``); evaluation happens on device at
    the same iterations the legacy loop evaluates (k = 0 mod eval_every, and
    k = T-1), so both engines emit identical ``SimResult`` trajectories.

    The function is jit-able and vmap-able over both ``policy_idx`` and
    ``(seed, idx)`` - ``repro.fl.sweep`` builds the policy x seed grid from
    exactly this function.
    """
    if sim.mix_impl == "sharded":
        # deferred import: repro.fl.sharded imports back into this module
        from repro.fl.sharded import make_sharded_engine

        eng, model_dim, _plan = make_sharded_engine(
            sim, graph, T=T, eval_every=eval_every, x=x, y=y, eval_fn=eval_fn)
        return eng, model_dim

    core = _EngineCore(sim, graph, eval_every=eval_every, x=x, y=y,
                       eval_fn=eval_fn)

    def engine(policy_idx, seed, idx):
        state, bw = core.init(seed)
        alphas = core.sched(jnp.arange(T))
        _, out = core.span(policy_idx, state, idx, alphas, final=True)
        return {**out, "bandwidths": bw}

    return engine, core.model_dim


# Compiled-engine cache for run(): the engine is policy- and seed-agnostic
# (both enter as traced arguments), so sequential runs over policies/seeds -
# the compare() fallback, parity tests, notebook loops - share ONE compile
# per (config, graph, data, eval) combination instead of recompiling the
# full horizon each call.  The graph enters the key BY VALUE (dataclass
# fields + base-adjacency bytes): two structurally identical GraphProcess
# instances must share a compile.  Data/eval stay id()-keyed; those entries
# keep their referents alive so a recycled id cannot alias a stale entry.
# The cache is a small LRU, instrumented so the scenario service can report
# compile reuse per request (ISSUE 8: hits were previously unobservable).


@dataclasses.dataclass
class EngineCacheStats:
    """Point-in-time counters for the compiled-engine LRU.

    ``hits``/``misses``/``evictions`` are lifetime (survive ``clear()``
    resets of the entries, reset only by ``reset_stats=True``); ``entries``
    and ``key_bytes`` describe the current contents -- ``key_bytes`` is the
    total size of the byte-valued key components (the lexsorted edge-list
    arrays), i.e. what "keyed on edge bytes O(E)" costs in cache memory."""

    hits: int = 0
    misses: int = 0
    evictions: int = 0
    entries: int = 0
    key_bytes: int = 0

    @property
    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "entries": self.entries,
                "key_bytes": self.key_bytes, "hit_rate": self.hit_rate}


def _key_nbytes(key) -> int:
    if isinstance(key, bytes):
        return len(key)
    if isinstance(key, tuple):
        return sum(_key_nbytes(k) for k in key)
    return 0


class EngineCache:
    """LRU of built (jitted engine, model_dim, keepalive) entries with
    hit/miss accounting.  Supports ``len()`` and ``clear()`` like the plain
    OrderedDict it replaces."""

    def __init__(self, size: int = 8):
        self.size = size
        self._d: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._hits = 0
        self._misses = 0
        self._evictions = 0

    def __len__(self) -> int:
        return len(self._d)

    def clear(self, *, reset_stats: bool = False) -> None:
        self._d.clear()
        if reset_stats:
            self._hits = self._misses = self._evictions = 0

    def get_or_build(self, key: tuple, build) -> tuple:
        hit = self._d.get(key)
        if hit is None:
            self._misses += 1
            hit = build()
            self._d[key] = hit
            while len(self._d) > self.size:
                self._d.popitem(last=False)
                self._evictions += 1
        else:
            self._hits += 1
            self._d.move_to_end(key)
        return hit

    def stats(self) -> EngineCacheStats:
        return EngineCacheStats(
            hits=self._hits, misses=self._misses, evictions=self._evictions,
            entries=len(self._d),
            key_bytes=sum(_key_nbytes(k) for k in self._d))


_ENGINE_CACHE = EngineCache(size=8)


def engine_cache_stats() -> EngineCacheStats:
    """Snapshot of the compiled-engine cache counters (public observability
    hook; the scenario service surfaces this in per-request reports)."""
    return _ENGINE_CACHE.stats()


def _graph_cache_key(graph: GraphProcess) -> tuple:
    """Value key for a GraphProcess: every field that shapes the compiled
    adjacency stream, with the fabric by content, not identity.  Hashing the
    canonical edge list (lexsorted, so layout is deterministic) keeps the
    key O(E) -- the old dense ``base.tobytes()`` key densified the graph and
    cost O(m^2) host bytes per engine build, which is exactly what the
    edge-native staging path exists to avoid at m >= 16384."""
    return (graph.kind, float(graph.drop), int(graph.cycle_len),
            int(graph.seed), graph.edges.m,
            graph.edges.u.tobytes(), graph.edges.v.tobytes())


def _cached_engine(sim: SimConfig, graph: GraphProcess, *, T: int,
                   eval_every: int, x, y, eval_fn):
    key = (sim.m, sim.model, sim.n_classes, sim.dim, sim.batch, sim.r,
           sim.b_mean, sim.sigma_n, sim.alpha0, sim.optimizer, sim.mix_impl,
           sim.trace, int(sim.shards), T, max(1, int(eval_every)),
           sim.churn_rate, sim.recover_rate, sim.straggle_rate, sim.bw_walk,
           sim.budget_bytes,
           sim.cluster_fail_rate, sim.cluster_recover_rate,
           int(sim.partition_start), int(sim.partition_len),
           sim.flap_rate, int(sim.flap_len), sim.crash_rate,
           sim.rejoin_rate, bool(sim.warm_start),
           int(sim.watchdog_window), int(sim.watchdog_nprop),
           _graph_cache_key(graph), id(x), id(y), id(eval_fn))

    def build():
        eng, model_dim = make_engine(sim, graph, T=T, eval_every=eval_every,
                                     x=x, y=y, eval_fn=eval_fn)
        return (jax.jit(eng), model_dim, (graph, x, y, eval_fn))

    hit = _ENGINE_CACHE.get_or_build(key, build)
    return hit[0], hit[1]


def _result_from_device(out: dict, model_dim: int, trace: str) -> SimResult:
    host = jax.device_get(out)  # the run's single host<->device sync
    return SimResult(
        loss=np.asarray(host["loss"], np.float32),
        acc=np.asarray(host["acc"], np.float32),
        tx_time=np.asarray(host["tx_time"], np.float32),
        util=np.asarray(host["util"], np.float32),
        v=np.asarray(host["v"], bool),
        comm_count=np.asarray(host["comm_count"], np.int32),
        deg=np.asarray(host["deg"], np.int32),
        consensus_err=np.asarray(host["consensus_err"], np.float32),
        model_dim=model_dim,
        bandwidths=np.asarray(host["bandwidths"], np.float32),
        trace=trace,
        _comm=(np.asarray(host["comm"], trace_mod.link_dtype(trace))
               if "comm" in host else None),
        _adj=(np.asarray(host["adj"], trace_mod.link_dtype(trace))
              if "adj" in host else None),
        down_count=np.asarray(host["down_count"], np.int32),
        exhausted_count=np.asarray(host["exhausted_count"], np.int32),
        fault_down_count=np.asarray(host["fault_down_count"], np.int32),
        stale_max=np.asarray(host["stale_max"], np.int32),
        window_connected=np.asarray(host["window_connected"], bool),
        window_needed=np.asarray(host["window_needed"], np.int32),
    )


def run(
    sim: SimConfig,
    graph: GraphProcess,
    batches: FederatedBatches,
    eval_fn: Callable[[np.ndarray], float] | EvalFn | None = None,
    *,
    eval_every: int = 10,
    engine: str = "scan",
) -> SimResult:
    """Simulates ``sim.iters`` universal iterations; returns ``SimResult``.

    ``engine="scan"`` stages the batch indices up front and runs the whole
    horizon as one compiled chunked-scan program (device-resident metrics,
    on-device eval).  ``engine="python"`` is the legacy per-step loop; it is
    also used automatically when ``eval_fn`` is a plain host callable that
    the compiled program cannot invoke.
    """
    if engine == "scan" and (eval_fn is None or isinstance(eval_fn, EvalFn)):
        eng, model_dim = _cached_engine(
            sim, graph, T=sim.iters, eval_every=eval_every,
            x=batches.x, y=batches.y, eval_fn=eval_fn)
        # host spans on the profiler's clock, beside the device's ops
        args = {"m": sim.m, "T": sim.iters}
        with jax.profiler.TraceAnnotation("sim.stage", **args):
            idx = batches.stage(sim.iters)
        with jax.profiler.TraceAnnotation("sim.launch", **args):
            out = eng(triggers.policy_index(sim.policy),
                      jnp.asarray(sim.seed, jnp.int32), jnp.asarray(idx))
        with jax.profiler.TraceAnnotation("sim.fetch", **args):
            return _result_from_device(out, model_dim, sim.trace)
    if sim.mix_impl == "sharded":
        raise ValueError(
            "mix_impl='sharded' runs only under engine='scan' with an "
            "EvalFn (or None): the shard_map program cannot call back into "
            "a host loop or a host eval callable")
    return _run_python(sim, graph, batches, eval_fn, eval_every=eval_every)


def _run_python(
    sim: SimConfig,
    graph: GraphProcess,
    batches: FederatedBatches,
    eval_fn,
    *,
    eval_every: int = 10,
) -> SimResult:
    """Reference engine: per-step host loop with per-iteration host copies.

    Kept for the scan-parity test and for custom host-side eval callables;
    new code should prefer ``engine="scan"``."""
    key = jax.random.PRNGKey(sim.seed)
    k_bw, k_init, k_state = jax.random.split(key, 3)
    m = sim.m
    bw = triggers.sample_bandwidths(k_bw, m, sim.b_mean, sim.sigma_n)

    spec = model_spec(sim)
    grad_fn = spec.grad_fn
    opt = init_opt(sim.optimizer)

    w0 = spec.init_stack(k_init, m)
    model_dim = spec.flat_dim

    cfg = _efhc_cfg(sim)
    sched = paper_diminishing(sim.alpha0, gamma=1.0, theta=0.5)
    sparse = cfg.mix_impl in efhc.SPARSE_MIX_IMPLS
    nl = (graph.neighbors()
          if sparse or cfg.watchdog is not None else None)
    adj0 = graph.adjacency_ell(0, nl) if sparse else graph.adjacency(0)
    rcfg = cfg.resources
    res0 = (resources_mod.init_state(
                rcfg, bw, resources_mod.resource_key(key, rcfg))
            if rcfg is not None else None)
    fcfg = cfg.faults
    if fcfg is not None:
        fab = faults_mod.fault_fabric(graph, fcfg)
        ftabs = (faults_mod.edge_tables_rows(fab, graph.edges, nl.idx, nl.mask)
                 if sparse else faults_mod.edge_tables_dense(fab, graph.edges))
        f0 = faults_mod.init_state(fcfg, fab, faults_mod.fault_key(key, fcfg))
    else:
        ftabs, f0 = None, None
    wd0 = (flow_mod.watchdog_init(m, nl.idx.shape[1])
           if cfg.watchdog is not None else None)
    state = efhc.init_state(w0, bw, adj0, k_state, opt_state=opt.init(w0),
                            resources=res0, faults=f0, watchdog=wd0)

    step_jit = jax.jit(
        lambda st, batch, alpha: efhc.step(
            cfg, graph, st, grad_fn=grad_fn, batch=batch, alpha_k=alpha,
            model_dim=model_dim, nl=nl, opt_update=opt.update, ftabs=ftabs
        )
    )

    T = sim.iters
    loss_t = np.zeros((T, m), np.float32)
    acc_t = np.zeros(T, np.float32)
    tx_t = np.zeros(T, np.float32)
    util_t = np.zeros(T, np.float32)
    v_t = np.zeros((T, m), bool)
    comm_t = np.zeros((T, m, m), bool)
    adj_t = np.zeros((T, m, m), bool)
    cons_t = np.zeros(T, np.float32)
    down_t = np.zeros(T, np.int32)
    exh_t = np.zeros(T, np.int32)
    fdown_t = np.zeros(T, np.int32)
    stale_t = np.zeros(T, np.int32)
    wconn_t = np.ones(T, bool)
    wneed_t = np.zeros(T, np.int32)

    last_acc = 0.0
    for k in range(T):
        xb, yb = batches.next()
        state, aux = step_jit(state, (jnp.asarray(xb), jnp.asarray(yb)), sched(k))
        loss_t[k] = np.asarray(aux.loss)
        tx_t[k] = float(aux.tx_time)
        util_t[k] = float(aux.util)
        v_t[k] = np.asarray(aux.v)
        comm_t[k] = np.asarray(aux.comm)
        adj_t[k] = np.asarray(aux.adj)
        cons_t[k] = float(aux.consensus_err)
        down_t[k] = int(aux.down_count)
        exh_t[k] = int(aux.exhausted_count)
        fdown_t[k] = int(aux.fault_down_count)
        stale_t[k] = int(aux.stale_max)
        wconn_t[k] = bool(aux.window_connected)
        wneed_t[k] = int(aux.window_needed)
        if eval_fn is not None and (k % eval_every == 0 or k == T - 1):
            last_acc = eval_fn(jax.device_get(state.w))
        acc_t[k] = last_acc

    trace = trace_mod.check_trace_mode(sim.trace)
    if trace == "packed":
        comm_s, adj_s = trace_mod.pack_links_np(comm_t), trace_mod.pack_links_np(adj_t)
    elif trace == "summary":
        comm_s = adj_s = None
    else:
        comm_s, adj_s = comm_t, adj_t
    return SimResult(
        loss=loss_t, acc=acc_t, tx_time=tx_t, util=util_t, v=v_t,
        comm_count=comm_t.sum(-1).astype(np.int32),
        deg=adj_t.sum(-1).astype(np.int32),
        consensus_err=cons_t, model_dim=model_dim,
        bandwidths=np.asarray(bw), trace=trace, _comm=comm_s, _adj=adj_s,
        down_count=down_t, exhausted_count=exh_t,
        fault_down_count=fdown_t, stale_max=stale_t,
        window_connected=wconn_t, window_needed=wneed_t,
    )


# ---------------------------------------------------------------------------
# crash-safe checkpoint/resume (DESIGN.md "Fault injection & resilience")
# ---------------------------------------------------------------------------

class CheckpointHalt(RuntimeError):
    """Raised by ``run_checkpointed(halt_after=...)`` right after a segment
    checkpoint lands -- the test harness's deterministic stand-in for a
    mid-run crash (kill -9 between segments)."""


def run_checkpointed(
    sim: SimConfig,
    graph: GraphProcess,
    batches: FederatedBatches,
    eval_fn: EvalFn | None = None,
    *,
    ckpt_dir: str,
    checkpoint_every: int,
    eval_every: int = 10,
    resume: bool = True,
    halt_after: int | None = None,
) -> SimResult:
    """Whole-horizon simulation with crash-safe segment checkpoints.

    The horizon is cut into segments of ``checkpoint_every`` iterations
    (which must be a multiple of ``eval_every``, so segment boundaries fall
    on chunk boundaries).  Each segment scans the SAME compiled chunk body
    the uninterrupted engine scans (``_EngineCore.span``), then persists the
    full carry -- ``EFHCState`` including ``opt_state``, ``ResourceState``,
    ``FaultState``, watchdog ages -- plus the segment's trajectories through
    the msgpack checkpoint layer (atomic tmp+rename writes; one
    ``step_<end>.msgpack`` per segment, never rotated).

    A later call with the same ``ckpt_dir`` and ``resume=True`` (the
    default) restores the newest carry and continues from there, re-running
    nothing; the assembled ``SimResult`` is bit-identical on EVERY channel
    to the uninterrupted checkpointed run (tests/test_checkpoint_resume.py).
    Relative to the one-shot ``run()`` engine the integer/bool channels
    (triggers, link counts, fault/watchdog verdicts) also match exactly;
    float channels agree to ULP-level tolerance only, because the one-shot
    engine compiles init + the whole horizon as a single XLA program with
    different fusion boundaries than the per-segment programs.
    ``halt_after=n`` raises ``CheckpointHalt`` after ``n`` segments --
    the deterministic crash used by the resume tests and the example.

    Batch staging stays deterministic across processes:
    ``FederatedBatches.stage(T)`` draws from the construction-seeded rng,
    so a fresh ``batches`` object in the resuming process stages the
    identical (T, m, batch) index tensor.  Its vectorised draw (one
    ``rng.integers`` call over all devices per chunk of iterations) is
    bit-exact to the per-device ``rng.choice`` loop, so this holds
    whichever process stages.
    """
    from repro.checkpoint import msgpack_ckpt

    if sim.mix_impl == "sharded":
        raise ValueError(
            "run_checkpointed drives the single-device chunked engine; "
            "mix_impl='sharded' is not checkpointable yet")
    E = max(1, int(eval_every))
    C = int(checkpoint_every)
    if C < 1 or C % E != 0:
        raise ValueError(
            f"checkpoint_every must be a positive multiple of eval_every "
            f"(segment boundaries must fall on eval-chunk boundaries); got "
            f"checkpoint_every={checkpoint_every}, eval_every={eval_every}")
    T = sim.iters
    core = _EngineCore(sim, graph, eval_every=E, x=batches.x, y=batches.y,
                       eval_fn=eval_fn)
    idx = jnp.asarray(batches.stage(T))
    pol = triggers.policy_index(sim.policy)
    meta = {"sim": dataclasses.asdict(sim), "T": int(T), "eval_every": int(E),
            "checkpoint_every": int(C)}

    done = 0
    ys_parts: list[dict] = []
    state = bw = None
    if resume:
        ends = msgpack_ckpt._steps(ckpt_dir)
        for end in ends:
            payload = msgpack_ckpt.restore(ckpt_dir, end)
            if payload.get("meta") != meta:
                raise ValueError(
                    f"checkpoint {ckpt_dir}/step_{end} was written by a "
                    f"different scenario (sim/T/eval_every/checkpoint_every "
                    f"mismatch); refusing to resume into it")
            ys_parts.append(payload["ys"])
            if end == ends[-1]:
                # leaves come back as exact-dtype numpy; None fields are
                # preserved by the codec and skipped by tree.map
                state = jax.tree.map(jnp.asarray, payload["state"])
                bw = jnp.asarray(payload["bandwidths"])
                done = int(end)
    if state is None:
        state, bw = core.init(int(sim.seed))

    # one jitted runner per ``final`` flag; jax re-specializes on segment
    # length automatically (at most two lengths: C and the T % C tail)
    seg_mid = jax.jit(lambda p, st, ix, al: core.span(p, st, ix, al,
                                                      final=False))
    seg_fin = jax.jit(lambda p, st, ix, al: core.span(p, st, ix, al,
                                                      final=True))

    segments_run = 0
    while done < T:
        end = min(done + C, T)
        runner = seg_fin if end == T else seg_mid
        alphas = core.sched(jnp.arange(done, end))
        state, out = runner(pol, state, idx[done:end], alphas)
        ys_host = jax.device_get(out)
        ys_parts.append(ys_host)
        msgpack_ckpt.save(
            ckpt_dir, end,
            {"meta": meta, "end": int(end), "state": state,
             "bandwidths": bw, "ys": ys_host},
            keep=0)  # keep every segment: earlier ys are part of the result
        done = end
        segments_run += 1
        if halt_after is not None and segments_run >= halt_after and done < T:
            raise CheckpointHalt(
                f"halted after {segments_run} segment(s) at iteration {done} "
                f"(checkpoint {ckpt_dir}/step_{done}.msgpack)")

    out_all = {k: np.concatenate([np.asarray(p[k]) for p in ys_parts], axis=0)
               for k in ys_parts[0]}
    out_all["bandwidths"] = np.asarray(jax.device_get(bw))
    return _result_from_device(out_all, core.model_dim, sim.trace)
