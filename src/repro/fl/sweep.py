"""Vmap-able policy x seed sweeps over the scan simulation engine.

The multi-seed / multi-policy grid is the paper's actual workload: every
Fig. 2 panel compares four trigger policies on shared data, and robust
claims (accuracy per transmission budget) need seed averaging.  The legacy
harness ran that grid as nested Python loops - serial, recompiling nothing
but syncing everything.  Here the whole grid is ONE compiled program:

    engine = simulator.make_engine(...)        # pure fn(policy_idx, seed, idx)
    grid   = vmap(vmap(engine, policy axis), seed axis)

Policies dispatch through ``lax.switch`` over ``triggers.policy_branches``
(so all four share the compiled step), and per-seed data/bandwidth/init
randomness rides the vmapped ``seed`` argument.  Batch indices are staged
per seed on the host (numpy rng) and gathered on device inside the scan.

``run_sweep`` returns a ``SweepResult`` holding the (S, P, T, ...) metric
stack; ``SweepResult.result(seed, policy)`` slices out a standard
``SimResult`` so downstream plotting/benchmark code is unchanged.

Fleet scale rides the same two SimConfig knobs as single runs: sweeps at
m >= 1024 want ``trace="summary"`` (the ys stay O(T m) per cell) and
``mix_impl="sparse"`` (neighbor-list Event-3, O(m d n) per iteration --
DESIGN.md "Sparse mixing"); the grid cells stay parity-exact with their
dense single-run counterparts (tests/test_scan_parity.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import triggers
from repro.core.topology import GraphProcess
from repro.data.loader import FederatedBatches
from repro.fl import simulator
from repro.fl import trace as trace_mod
from repro.fl.simulator import EvalFn, SimConfig, SimResult


@dataclasses.dataclass
class SweepResult:
    """Stacked trajectories for a seeds x policies grid.

    Metric arrays lead with (S, P) = (len(seeds), len(policies)); the
    remaining axes match ``SimResult`` (T per-iteration, m per-device).
    Like ``SimResult``, the ``comm``/``adj`` link matrices are accessors
    over ``trace``-dependent storage (dense / bit-packed / absent); slicing
    via ``result()`` keeps the storage mode, so a packed sweep stays packed
    until a cell's matrices are actually read.
    """

    seeds: tuple[int, ...]
    policies: tuple[str, ...]
    loss: np.ndarray  # (S, P, T, m)
    acc: np.ndarray  # (S, P, T)
    tx_time: np.ndarray  # (S, P, T)
    util: np.ndarray  # (S, P, T)
    v: np.ndarray  # (S, P, T, m)
    comm_count: np.ndarray  # (S, P, T, m) int32
    deg: np.ndarray  # (S, P, T, m) int32
    consensus_err: np.ndarray  # (S, P, T)
    bandwidths: np.ndarray  # (S, P, m) (policy axis is redundant but cheap)
    model_dim: int
    trace: str = "full"
    _comm: np.ndarray | None = None  # (S,P,T,m,m) bool | (S,P,T,m,W) uint32
    _adj: np.ndarray | None = None
    # resource channels (S, P, T) int32; all-zero without a resource process
    down_count: np.ndarray | None = None
    exhausted_count: np.ndarray | None = None
    # fault channels (S, P, T) int32; all-zero without a fault process
    fault_down_count: np.ndarray | None = None
    stale_max: np.ndarray | None = None
    # watchdog channels (S, P, T); all-True / all-zero without a watchdog
    window_connected: np.ndarray | None = None
    window_needed: np.ndarray | None = None

    @property
    def m(self) -> int:
        return int(self.bandwidths.shape[-1])

    @property
    def comm(self) -> np.ndarray:  # (S, P, T, m, m) bool
        return trace_mod.stored_links(self._comm, self.trace, self.m, "comm")

    @property
    def adj(self) -> np.ndarray:  # (S, P, T, m, m) bool
        return trace_mod.stored_links(self._adj, self.trace, self.m, "adj")

    def result(self, seed: int, policy: str) -> SimResult:
        """Slice one grid cell back out as a standard ``SimResult``."""
        s = self.seeds.index(seed)
        p = self.policies.index(policy)
        return SimResult(
            loss=self.loss[s, p], acc=self.acc[s, p], tx_time=self.tx_time[s, p],
            util=self.util[s, p], v=self.v[s, p],
            comm_count=self.comm_count[s, p], deg=self.deg[s, p],
            consensus_err=self.consensus_err[s, p],
            model_dim=self.model_dim, bandwidths=self.bandwidths[s, p],
            trace=self.trace,
            _comm=None if self._comm is None else self._comm[s, p],
            _adj=None if self._adj is None else self._adj[s, p],
            down_count=(None if self.down_count is None
                        else self.down_count[s, p]),
            exhausted_count=(None if self.exhausted_count is None
                             else self.exhausted_count[s, p]),
            fault_down_count=(None if self.fault_down_count is None
                              else self.fault_down_count[s, p]),
            stale_max=(None if self.stale_max is None
                       else self.stale_max[s, p]),
            window_connected=(None if self.window_connected is None
                              else self.window_connected[s, p]),
            window_needed=(None if self.window_needed is None
                           else self.window_needed[s, p]),
        )

    @property
    def cum_tx_time(self) -> np.ndarray:
        return np.cumsum(self.tx_time, axis=-1)


def run_sweep(
    sim: SimConfig,
    graph: GraphProcess,
    batches_factory: Callable[[int], FederatedBatches],
    eval_fn: EvalFn | None = None,
    *,
    seeds: Sequence[int] = (0,),
    policies: Sequence[str] = triggers.POLICIES,
    eval_every: int = 10,
) -> SweepResult:
    """Runs the full seeds x policies grid in a single compiled call.

    ``batches_factory(seed)`` supplies the per-seed federated sampler (all
    policies within a seed share its staged batches, matching the legacy
    compare() protocol of identical data across policies).  ``sim.seed`` and
    ``sim.policy`` are ignored in favor of the grid axes.
    """
    if eval_fn is not None and not isinstance(eval_fn, EvalFn):
        raise TypeError(
            "run_sweep folds evaluation into the compiled program and needs "
            "an EvalFn (e.g. from simulator.make_eval_fn) or None; a plain "
            "host callable cannot run inside jit - use simulator.run("
            "engine='python') for that.")
    seeds = tuple(int(s) for s in seeds)
    policies = tuple(policies)
    T = sim.iters
    if sim.mix_impl == "sharded":
        return _run_sweep_sharded(sim, graph, batches_factory, eval_fn,
                                  seeds=seeds, policies=policies,
                                  eval_every=eval_every)

    staged, ref = [], None
    for s in seeds:
        b = batches_factory(s)
        ref = ref if ref is not None else b
        if ((b.x is not ref.x and not np.array_equal(b.x, ref.x))
                or (b.y is not ref.y and not np.array_equal(b.y, ref.y))):
            raise ValueError(
                "all batches_factory(seed) samplers must share one dataset: "
                "staged indices are gathered against the first seed's (x, y) "
                "arrays; vary the *sampling* seed per seed, not the data.")
        staged.append(b.stage(T))
    idx = jnp.asarray(np.stack(staged))  # (S, T, m, batch)

    engine, model_dim = simulator.make_engine(
        sim, graph, T=T, eval_every=eval_every, x=ref.x, y=ref.y, eval_fn=eval_fn)

    policy_idx = jnp.asarray([triggers.policy_index(p) for p in policies], jnp.int32)
    seed_arr = jnp.asarray(seeds, jnp.int32)

    over_policies = jax.vmap(engine, in_axes=(0, None, None))
    grid = jax.jit(jax.vmap(over_policies, in_axes=(None, 0, 0)))
    out = jax.device_get(grid(policy_idx, seed_arr, idx))

    trace = trace_mod.check_trace_mode(sim.trace)
    link_dtype = trace_mod.link_dtype(trace)
    return SweepResult(
        seeds=seeds, policies=policies,
        loss=np.asarray(out["loss"], np.float32),
        acc=np.asarray(out["acc"], np.float32),
        tx_time=np.asarray(out["tx_time"], np.float32),
        util=np.asarray(out["util"], np.float32),
        v=np.asarray(out["v"], bool),
        comm_count=np.asarray(out["comm_count"], np.int32),
        deg=np.asarray(out["deg"], np.int32),
        consensus_err=np.asarray(out["consensus_err"], np.float32),
        bandwidths=np.asarray(out["bandwidths"], np.float32),
        model_dim=model_dim,
        trace=trace,
        _comm=(np.asarray(out["comm"], link_dtype) if "comm" in out else None),
        _adj=(np.asarray(out["adj"], link_dtype) if "adj" in out else None),
        down_count=np.asarray(out["down_count"], np.int32),
        exhausted_count=np.asarray(out["exhausted_count"], np.int32),
        fault_down_count=np.asarray(out["fault_down_count"], np.int32),
        stale_max=np.asarray(out["stale_max"], np.int32),
        window_connected=np.asarray(out["window_connected"], bool),
        window_needed=np.asarray(out["window_needed"], np.int32),
    )


def _run_sweep_sharded(sim, graph, batches_factory, eval_fn, *,
                       seeds, policies, eval_every) -> SweepResult:
    """Grid over the sharded fleet engine: cells run serially through
    ``simulator.run`` instead of one vmapped program.  vmap over the
    shard_map engine does compose (equal to solo cells on every channel
    at m=8 over 2 host devices, ``tests/sharded_worker.py vmap``), but at
    the fleet sizes that want sharding (m >= 10^5) a batched grid would
    not fit.  The engine takes
    policy/seed as traced arguments, so every cell still shares ONE
    compile via the simulator's engine cache; only the executions
    serialize."""
    cells = [[simulator.run(
        dataclasses.replace(sim, seed=s, policy=p), graph,
        batches_factory(s), eval_fn, eval_every=eval_every)
        for p in policies] for s in seeds]
    stack = lambda f, dt: np.stack(
        [[np.asarray(getattr(c, f), dt) for c in row] for row in cells])
    return SweepResult(
        seeds=seeds, policies=policies,
        loss=stack("loss", np.float32), acc=stack("acc", np.float32),
        tx_time=stack("tx_time", np.float32), util=stack("util", np.float32),
        v=stack("v", bool), comm_count=stack("comm_count", np.int32),
        deg=stack("deg", np.int32),
        consensus_err=stack("consensus_err", np.float32),
        bandwidths=stack("bandwidths", np.float32),
        model_dim=cells[0][0].model_dim,
        trace=trace_mod.check_trace_mode(sim.trace),
        down_count=stack("down_count", np.int32),
        exhausted_count=stack("exhausted_count", np.int32),
        fault_down_count=stack("fault_down_count", np.int32),
        stale_max=stack("stale_max", np.int32),
        window_connected=stack("window_connected", bool),
        window_needed=stack("window_needed", np.int32),
    )


# ---------------------------------------------------------------------------
# robust sweep metrics (paper Fig. 2-(iii) as an area, not a point)
# ---------------------------------------------------------------------------

def acc_per_tx_auc(acc: np.ndarray, cum_tx: np.ndarray, budget: float) -> float:
    """Area under the accuracy-vs-cumulative-transmission-time curve up to
    ``budget``, normalized by ``budget`` (so the value is a mean accuracy
    over the budget interval, in [0, 1]).

    This is the paper's Fig. 2-(iii) claim made robust: instead of comparing
    accuracies at one budget point (noisy - a single eval step can flip it),
    integrate the whole trade-off curve.  The curve is the step function
    acc(t) = acc[k] for t in [cum_tx[k-1], cum_tx[k])."""
    edges = np.concatenate([[0.0], np.minimum(cum_tx, budget)])
    widths = np.clip(np.diff(edges), 0.0, None)
    area = float((widths * acc[: len(widths)]).sum())
    tail = budget - float(edges[-1])
    if tail > 0:  # curve exhausted before the budget: hold the last accuracy
        area += tail * float(acc[-1])
    return area / budget if budget > 0 else 0.0


def policy_auc_table(res: SweepResult, *, budget_frac: float = 0.9) -> dict[str, np.ndarray]:
    """Per-policy accuracy-per-tx AUC, seed by seed: {policy: (S,) array}.

    The budget is shared across policies within each seed (the smallest
    total transmission time, scaled by ``budget_frac``), mirroring the
    Fig. 2-(iii) protocol."""
    cum = res.cum_tx_time  # (S, P, T)
    out = {p: np.zeros(len(res.seeds)) for p in res.policies}
    for s in range(len(res.seeds)):
        budget = float(cum[s, :, -1].min()) * budget_frac
        for p, name in enumerate(res.policies):
            out[name][s] = acc_per_tx_auc(res.acc[s, p], cum[s, p], budget)
    return out
