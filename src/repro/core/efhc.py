"""EF-HC: the full four-event algorithm (paper Alg. 1) as a jittable step.

State kept per device i (paper Sec. II-A):
  * w_i      - instantaneous main model
  * w_hat_i  - auxiliary (last broadcast) model
plus shared bookkeeping: iteration k, previous adjacency (to detect Event-1
neighbor connections), bandwidths b_i, PRNG key.

The universal iteration k drives: the graph process (Event 1), trigger
evaluation (Event 2), P-matrix mixing (Event 3) and the SGD step (Event 4).
``step`` is pure; the simulator (repro/fl) scans it.

Event semantics under one jitted program: when no event fires on a link,
v_ij = 0 => p_ij = 0 and the mixing leaves w_i untouched -- mathematically
identical to skipping the transmission (see DESIGN.md "Event semantics under
SPMD" for how communication savings are accounted).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import accounting, consensus, mixing, topology, triggers
from repro.core import faults as faults_mod
from repro.core import flow as flow_mod
from repro.core import resources as resources_mod
from repro.core.topology import GraphProcess
from repro.kernels.mixing import ops as mixing_ops
from repro.kernels.trigger import ops as trigger_ops


class EFHCState(NamedTuple):
    w: Any  # pytree, leaves (m, ...): per-device main models
    w_hat: Any  # pytree, leaves (m, ...): last-broadcast models
    k: jax.Array  # scalar int32 universal iteration
    # adjacency at k-1 for Event-1 detection: (m, m) bool dense, or the
    # (m, d_max) ELL slot mask under a sparse mix_impl (same edge set)
    prev_adj: jax.Array
    bandwidths: jax.Array  # (m,)
    key: jax.Array
    opt_state: Any = None
    # resource-dynamics carry (live bandwidth / budgets / liveness), None
    # unless cfg.resources is enabled (DESIGN.md "Resource dynamics")
    resources: Any = None
    # correlated-fault carry (crash bits / staleness / cluster outages),
    # None unless cfg.faults is enabled (DESIGN.md "Fault injection")
    faults: Any = None
    # B-connectivity watchdog carry (per-slot edge ages), None unless
    # cfg.watchdog is enabled
    watchdog: Any = None


MIX_IMPLS: tuple[str, ...] = ("dense", "delta", "pallas",
                              "sparse", "sparse_delta", "sparse_pallas")
# impls that run Events 1/3 in neighbor-list (ELL) layout; state.prev_adj
# is the (m, d_max) slot mask and the (m, m) matrices exist only as
# DCE-able debris for StepAux consumers (DESIGN.md "Sparse mixing")
SPARSE_MIX_IMPLS: tuple[str, ...] = ("sparse", "sparse_delta", "sparse_pallas")


@dataclasses.dataclass(frozen=True)
class EFHCConfig:
    trigger: triggers.TriggerConfig = dataclasses.field(default_factory=triggers.TriggerConfig)
    # gamma^(k): decaying factor; paper Sec. IV-A sets gamma^(k) = alpha^(k)
    gamma: Callable[[jax.Array], jax.Array] = None  # type: ignore[assignment]
    # "pallas" routes Event-3 aggregation through the fused mixing kernel and
    # the Event-2 deviation through the fused trigger kernel (DESIGN.md
    # "Pallas hot path"); "dense"/"delta" are the pure-jnp references.
    # "sparse"/"sparse_delta" (pure-jnp gather) and "sparse_pallas" (fused
    # gather-mix kernel) aggregate over the padded neighbor list instead of
    # the (m, m) matrix -- the m >= 4096 path (DESIGN.md "Sparse mixing").
    mix_impl: str = "dense"  # see MIX_IMPLS
    # Pallas interpret mode: None = auto (interpret off only on TPU)
    interpret: bool | None = None
    # resource dynamics (churn/stragglers/budgets/bandwidth walk); None or a
    # disabled config keeps the step structurally identical to the
    # pre-resource program -- the gate is a Python-level branch, so golden
    # trajectories stay bit-exact (DESIGN.md "Resource dynamics")
    resources: resources_mod.ResourceConfig | None = None
    # correlated fault injection (cluster outages / scripted partition /
    # flapping links / crash-rejoin); the same Python-level-gate contract
    # as ``resources`` (DESIGN.md "Fault injection & resilience")
    faults: faults_mod.FaultConfig | None = None
    # in-scan B-connectivity watchdog over the information-flow graph;
    # None or window=0 keeps the step structurally watchdog-free
    watchdog: flow_mod.WatchdogConfig | None = None

    def resources_enabled(self) -> bool:
        return self.resources is not None and self.resources.enabled

    def faults_enabled(self) -> bool:
        return self.faults is not None and self.faults.enabled

    def watchdog_enabled(self) -> bool:
        return self.watchdog is not None and self.watchdog.enabled

    def pallas_interpret(self) -> bool:
        if self.interpret is not None:
            return bool(self.interpret)
        return jax.default_backend() != "tpu"


def init_state(w_stack, bandwidths: jax.Array, adjacency0: jax.Array, key: jax.Array, opt_state=None, resources=None, faults=None, watchdog=None) -> EFHCState:
    return EFHCState(
        w=w_stack,
        w_hat=jax.tree.map(jnp.copy, w_stack),
        k=jnp.asarray(0, jnp.int32),
        prev_adj=adjacency0,
        bandwidths=bandwidths,
        key=key,
        opt_state=opt_state,
        resources=resources,
        faults=faults,
        watchdog=watchdog,
    )


def _flatten_stack(w_stack) -> jax.Array:
    """Canonical (m, D) flat view of the per-device model pytree: leaves
    concatenated in ``jax.tree.leaves`` order, cast to float32.  Events 1-3
    (triggers, deviation kernel, gather-mix) always operate on this view;
    ``unflatten_stack`` is the inverse (DESIGN.md "Model plumbing")."""
    leaves = jax.tree.leaves(w_stack)
    m = leaves[0].shape[0]
    return jnp.concatenate([l.reshape(m, -1).astype(jnp.float32) for l in leaves], axis=1)


# public alias: the simulator/tests use the flat view as the model-agnostic
# row layout, not just an internal detail
flatten_stack = _flatten_stack


def unflatten_stack(flat: jax.Array, like):
    """Inverse of ``_flatten_stack``: slice the (m, D) flat rows back into
    the pytree structure, shapes and dtypes of ``like``.  Column order is
    the same ``jax.tree.leaves`` order the flatten used, so
    ``unflatten_stack(_flatten_stack(w), w)`` is an exact round trip for
    float32 leaves (and a cast for anything narrower)."""
    leaves, treedef = jax.tree.flatten(like)
    out, col = [], 0
    for l in leaves:
        n = math.prod(l.shape[1:])
        out.append(flat[:, col:col + n].reshape(l.shape).astype(l.dtype))
        col += n
    return jax.tree.unflatten(treedef, out)


class StepAux(NamedTuple):
    """Everything the paper's plots need, emitted per iteration so a
    ``lax.scan`` over ``step`` accumulates full trajectories on device
    (no per-step host copies - see DESIGN.md "Scan engine")."""

    v: jax.Array  # (m,) broadcast events fired
    comm: jax.Array  # (m, m) links used (information-flow edges E'^(k))
    p: jax.Array  # (m, m) transition matrix
    loss: jax.Array  # (m,) per-device minibatch loss
    tx_time: jax.Array  # scalar: avg transmission time this iteration
    util: jax.Array  # scalar: resource utilization score
    adj: jax.Array  # (m, m) physical adjacency G^(k) (B-connectivity checks)
    consensus_err: jax.Array  # scalar: ||W - 1 w_bar||_F^2 after the update
    # per-device row sums, first-class so summary-trace ys never touch the
    # (m, m) matrices above (under a sparse mix_impl those are scatters
    # that XLA dead-code-eliminates when nothing reads them)
    comm_count: jax.Array  # (m,) int32: links used per device
    deg: jax.Array  # (m,) int32: physical degree per device
    # resource-dynamics counters (zeros when disabled): devices down via
    # churn / out of broadcast budget this iteration
    down_count: jax.Array  # scalar int32
    exhausted_count: jax.Array  # scalar int32
    # fault-injection counters (zeros when disabled): devices silenced by
    # crash or cluster outage / worst staleness carried by a crashed device
    fault_down_count: jax.Array  # scalar int32
    stale_max: jax.Array  # scalar int32
    # watchdog channels (True / 0 when disabled): is the sliding union
    # window connected, and the smallest window that would connect it
    window_connected: jax.Array  # scalar bool
    window_needed: jax.Array  # scalar int32


def _mask_update_rows(upd: jax.Array, m: int, new_tree, old_tree):
    """Event-4 straggler/churn mask: rows of ``new_tree`` where ``upd`` is
    False are replaced by ``old_tree``'s.  Leaves without a leading device
    axis (e.g. Adam's step count) pass through -- they are fleet-global."""

    def keep(new_leaf, old_leaf):
        if new_leaf.ndim >= 1 and new_leaf.shape[0] == m:
            mask = upd.reshape((m,) + (1,) * (new_leaf.ndim - 1))
            return jnp.where(mask, new_leaf, old_leaf)
        return new_leaf

    return jax.tree.map(keep, new_tree, old_tree)


def step(
    cfg: EFHCConfig,
    graph: GraphProcess,
    state: EFHCState,
    *,
    grad_fn: Callable[[Any, jax.Array, Any], tuple[jax.Array, Any]],
    batch,
    alpha_k: jax.Array,
    model_dim: int,
    policy_idx: jax.Array | None = None,
    nl: topology.NeighborList | None = None,
    opt_update: Callable[[Any, Any, Any, jax.Array], tuple[Any, Any]] | None = None,
    ftabs: faults_mod.FaultTabs | None = None,
) -> tuple[EFHCState, StepAux]:
    """One universal iteration of Alg. 1 across all m devices.

    grad_fn(w_i, key, batch_i) -> (loss_i, grad_i) for a single device;
    it is vmapped over the leading device axis here.

    ``policy_idx``: optional traced index into ``triggers.POLICIES``; when
    given, the trigger policy is dispatched via ``lax.switch`` so the same
    compiled step serves every policy (vmap-able policy axis).

    ``nl``: the base graph's neighbor list, required context under a sparse
    mix_impl; callers that already built one (the engines) pass it so the
    host-side construction isn't repeated per trace.  Both the neighbor
    list and the graph's canonical ``EdgeList`` fabric are O(E) host
    staging -- nothing on this path densifies an (m, m) matrix, which is
    what lets the sparse impls step m >= 16384 fleets.

    ``opt_update``: a functional ``repro.optim`` update,
    ``(grads, opt_state, params, lr) -> (new_params, new_opt_state)``,
    applied to the stacked pytree for Event 4 (every provided optimizer is
    elementwise over the device axis, so stacked application == vmap).
    ``None`` keeps the inline SGD expression -- bit-identical to
    ``optimizers.sgd()``, which is what the engines pass by default.

    Events 1-3 run on the canonical (m, D) flat view (one flatten at the
    top, one ``unflatten_stack`` before Event 4); only local SGD and the
    w_hat snapshot see the pytree (DESIGN.md "Model plumbing").

    Each part runs under a ``jax.named_scope`` -- ``efhc.event1`` (key
    split, adjacency, churn/fault masks, new links), ``efhc.event2``
    (deviation, triggers), ``efhc.event3`` (P, mix, rejoin, watchdog,
    w_hat), ``efhc.event4`` (local update), ``efhc.stats`` -- so a profile
    attributes each device op to its Event.  Scopes are op metadata only:
    the compiled program and its numerics do not change."""
    if cfg.mix_impl not in MIX_IMPLS:
        raise ValueError(f"unknown mix_impl {cfg.mix_impl!r}; known: {MIX_IMPLS}")
    sparse = cfg.mix_impl in SPARSE_MIX_IMPLS
    m = state.bandwidths.shape[0]
    with jax.named_scope("efhc.event1"):
        key, k_trig, k_grad = jax.random.split(state.key, 3)

        # resource dynamics: Python-level gate -- the disabled path is the
        # pre-resource program verbatim (no extra RNG splits, no masking ops)
        rcfg = cfg.resources
        dyn = rcfg is not None and rcfg.enabled
        if dyn:
            res = state.resources
            r_key, k_evolve = jax.random.split(res.key)
            up, straggle, bw_live = resources_mod.evolve(
                rcfg, k_evolve, res.up, res.bw, state.bandwidths, m)
            exhausted = resources_mod.exhausted_mask(rcfg, res.budget)
            # exhausted devices see a collapsed threshold bandwidth: rho = 1/b
            # explodes and the personalized trigger goes quiet on its own
            bw_thresh = jnp.where(
                exhausted, resources_mod.EXHAUSTED_BW_FRAC * state.bandwidths,
                bw_live)
        else:
            bw_thresh = state.bandwidths
            bw_live = state.bandwidths

        # correlated faults: an independent Python-level gate with its own
        # carried stream -- crash/rejoin + cluster-outage Markov bits evolve
        # here; edge-level faults (partition window, flapping) mask below
        fcfg = cfg.faults
        fdyn = fcfg is not None and fcfg.enabled
        if fdyn:
            fstate = state.faults
            f_key, k_fevolve = jax.random.split(fstate.key)
            crashed, rejoined, staleness, cluster_down = faults_mod.evolve(
                fcfg, k_fevolve, fstate.crashed, fstate.staleness,
                fstate.cluster_down, m)
            f_up = faults_mod.device_up(crashed, cluster_down, ftabs.labels)

        wcfg = cfg.watchdog
        wdog = wcfg is not None and wcfg.enabled

        if sparse:
            if nl is None:
                # setup-time numpy, traced in as constants; built straight from
                # the edge list (vectorized, never via a dense adjacency)
                nl = graph.neighbors()
            nbr_idx = jnp.asarray(nl.idx)
            adj_ell = graph.adjacency_ell(state.k, nl)
            if dyn:
                # churn masks Events 1-3: a down endpoint removes the edge from
                # the effective G^(k); reconnection later fires Event 1 through
                # the ordinary prev-adjacency delta
                adj_ell = jnp.logical_and(
                    adj_ell, jnp.logical_and(up[:, None], up[nbr_idx]))
            if fdyn:
                # crashed / clustered-out devices drop off the fabric entirely;
                # edge faults kill individual links on their own schedule
                adj_ell = jnp.logical_and(
                    adj_ell, jnp.logical_and(f_up[:, None], f_up[nbr_idx]))
                if fcfg.edge_faults:
                    adj_ell = jnp.logical_and(
                        adj_ell, faults_mod.edge_keep(fcfg, state.k, ftabs))
            # dense view for StepAux consumers only; dead code whenever the ys
            # stick to the ELL-derived row sums (trace="summary")
            adj = topology.scatter_ell(nbr_idx, adj_ell)
        else:
            adj = graph.adjacency(state.k)
            if dyn:
                adj = jnp.logical_and(
                    adj, jnp.logical_and(up[:, None], up[None, :]))
            if fdyn:
                adj = jnp.logical_and(
                    adj, jnp.logical_and(f_up[:, None], f_up[None, :]))
                if fcfg.edge_faults:
                    adj = jnp.logical_and(
                        adj, faults_mod.edge_keep(fcfg, state.k, ftabs))

    # ---- Event 2: broadcast triggers -------------------------------------
    with jax.named_scope("efhc.event2"):
        w_flat = _flatten_stack(state.w)
        w_hat_flat = _flatten_stack(state.w_hat)
        gamma_k = cfg.gamma(state.k) if cfg.gamma is not None else alpha_k
        if cfg.mix_impl == "pallas":
            # fused deviation kernel: streams (w, w_hat) tiles through VMEM
            # without materializing the delta in HBM
            n_model = w_flat.shape[1]
            sq = trigger_ops.trigger_sq(w_flat, w_hat_flat,
                                        interpret=cfg.pallas_interpret())
            dev = jnp.sqrt(sq / n_model)
        else:
            dev = triggers.rms_deviation(w_flat, w_hat_flat)
        v = triggers.broadcast_events(
            cfg.trigger, dev=dev,
            bandwidths=bw_thresh, gamma_k=gamma_k, key=k_trig,
            policy_idx=policy_idx,
        )
        if dyn:
            # hard mask: down and budget-exhausted devices fire nothing -- this
            # also stops the threshold-blind policies (ZT/gossip) from spending
            # past their budget
            v = jnp.logical_and(v, jnp.logical_and(up, ~exhausted))
        if fdyn:
            # crashed / clustered-out devices broadcast nothing
            v = jnp.logical_and(v, f_up)

    # ---- Event 1: neighbor connection ------------------------------------
    # Links that newly appeared vs k-1 exchange parameters unconditionally.
    # ---- Event 3: aggregation over the information-flow edges ------------
    if sparse:
        # same event algebra, per neighbor-list slot: prev_adj is the ELL
        # mask of G^(k-1), v_ij = v_i | v_j gathers the neighbor's trigger
        with jax.named_scope("efhc.event1"):
            new_links_ell = jnp.logical_and(adj_ell, ~state.prev_adj)
        with jax.named_scope("efhc.event3"):
            vv_ell = jnp.logical_or(v[:, None], v[nbr_idx])
            comm_ell = jnp.logical_or(jnp.logical_and(vv_ell, adj_ell), new_links_ell)
            p_diag, p_off = mixing.build_p_ell(nbr_idx, adj_ell, comm_ell)
            if cfg.mix_impl == "sparse_pallas":
                w_mixed_flat = mixing_ops.mix_sparse(nbr_idx, p_diag, p_off, w_flat,
                                                     interpret=cfg.pallas_interpret())
            elif cfg.mix_impl == "sparse_delta":
                w_mixed_flat = consensus.mix_delta_sparse(nbr_idx, p_off, w_flat)
            else:
                w_mixed_flat = consensus.mix_sparse(nbr_idx, p_diag, p_off, w_flat)
            comm = topology.scatter_ell(nbr_idx, comm_ell)  # DCE-able, like adj
            p = topology.scatter_ell(nbr_idx, p_off) + jnp.diag(p_diag)
            used_i = comm_ell.sum(axis=1, dtype=jnp.int32)
            deg_i = adj_ell.sum(axis=1, dtype=jnp.int32)
            prev_adj_next = adj_ell
    else:
        with jax.named_scope("efhc.event1"):
            new_links = jnp.logical_and(adj, ~state.prev_adj)
        with jax.named_scope("efhc.event3"):
            comm = jnp.logical_or(triggers.communication_matrix(v, adj), new_links)
            p = mixing.build_p(adj, comm)
            if cfg.mix_impl == "pallas":
                w_mixed_flat = mixing_ops.mix(p, w_flat, interpret=cfg.pallas_interpret())
            elif cfg.mix_impl == "delta":
                w_mixed_flat = consensus.mix_delta_dense(p, w_flat)
            else:
                w_mixed_flat = consensus.mix_dense(p, w_flat)
            used_i = comm.sum(axis=1, dtype=jnp.int32)
            deg_i = adj.sum(axis=1, dtype=jnp.int32)
            prev_adj_next = adj

    with jax.named_scope("efhc.event3"):
        if fdyn and fcfg.warm_start:
            # staleness-aware rejoin (ROADMAP recovery item (d)): a device
            # rejoining this iteration replaces its frozen stale model with the
            # plain average of its *live* neighbors' pre-mix models, instead of
            # re-entering consensus self-weighted by Metropolis p_ii.  Computed
            # from w_flat (pre-patch values), so multiple simultaneous rejoins
            # are order-independent -- and shard-consistent.
            if sparse:
                nb_sum = jnp.where(adj_ell[..., None], w_flat[nbr_idx], 0.0
                                   ).sum(axis=1)
                nb_cnt = adj_ell.sum(axis=1, dtype=jnp.float32)
            else:
                a_f = adj.astype(jnp.float32)
                nb_sum = jnp.matmul(a_f, w_flat, precision=consensus.MIX_PRECISION)
                nb_cnt = a_f.sum(axis=1)
            nb_avg = nb_sum / jnp.maximum(nb_cnt, 1.0)[:, None]
            patch = jnp.logical_and(rejoined, nb_cnt > 0)
            w_mixed_flat = jnp.where(patch[:, None], nb_avg, w_mixed_flat)

        # in-scan B-connectivity watchdog over the realized information-flow
        # edges E'^(k); under a dense mix_impl the (m, m) comm matrix is
        # gathered into ELL slots first (the engines pass ``nl`` whenever the
        # watchdog is on)
        if wdog:
            if sparse:
                w_idx, w_comm = nbr_idx, comm_ell
            else:
                w_idx = jnp.asarray(nl.idx)
                w_comm = flow_mod.comm_ell_from_dense(
                    comm, w_idx, jnp.asarray(nl.mask))
            wd_age, window_connected, window_needed = flow_mod.watchdog_step(
                wcfg, w_idx, w_comm, state.watchdog.age)
            wd_new = flow_mod.WatchdogState(age=wd_age)
        else:
            wd_new = state.watchdog
            window_connected = jnp.ones((), bool)
            window_needed = jnp.zeros((), jnp.int32)

        # w_hat update: devices that broadcast snapshot their *pre-mix* model
        # (Alg. 1 line 12: w_hat^(k+1) = w^(k))
        def upd_hat(h, wcur):
            mask = v.reshape((m,) + (1,) * (wcur.ndim - 1))
            return jnp.where(mask, wcur, h)

        w_hat_new = jax.tree.map(upd_hat, state.w_hat, state.w)

    # ---- Event 4: local SGD (on the unflattened pytree) -------------------
    with jax.named_scope("efhc.event4"):
        w_mixed = unflatten_stack(w_mixed_flat, state.w)
        grad_keys = jax.random.split(k_grad, m)
        loss, grads = jax.vmap(grad_fn, in_axes=(0, 0, 0))(w_mixed, grad_keys, batch)
        if opt_update is None:
            w_new = jax.tree.map(lambda wm, g: (wm.astype(jnp.float32) - alpha_k * g.astype(jnp.float32)).astype(wm.dtype), w_mixed, grads)
            opt_state_new = state.opt_state
        else:
            w_new, opt_state_new = opt_update(grads, state.opt_state, w_mixed, alpha_k)
        if dyn or fdyn:
            # stragglers delay Event 4 (carry the mixed model); down / crashed
            # devices do not compute at all -- both keep their pre-update rows
            # + opt state (a crashed device's edges are all masked, so its
            # "mixed" row IS its frozen theta)
            upd = None
            if dyn:
                upd = jnp.logical_and(up, ~straggle)
            if fdyn:
                upd = f_up if upd is None else jnp.logical_and(upd, f_up)
            w_new = _mask_update_rows(upd, m, w_new, w_mixed)
            opt_state_new = _mask_update_rows(upd, m, opt_state_new,
                                              state.opt_state)

    # ---- paper metrics (Sec. IV-A) ----------------------------------------
    with jax.named_scope("efhc.stats"):
        deg = deg_i.astype(jnp.float32)
        used = used_i.astype(jnp.float32)
        frac = jnp.where(deg > 0, used / jnp.maximum(deg, 1.0), 0.0)
        tx_time = jnp.mean(frac * model_dim / bw_live)
        # resource utilization (Sec. IV-A): fraction of the network's aggregate
        # one-hop link capacity consumed this iteration -- bits pushed over the
        # activated links vs. the capacity of every physical link.  A ratio of
        # sums, NOT the mean of per-device ratios (that would collapse back into
        # tx_time): heterogeneous bandwidths weight the two differently.
        capacity = jnp.sum(deg * bw_live)
        util = jnp.sum(used * model_dim) / jnp.maximum(capacity, 1e-12)

        # consensus error on the post-update stack (the paper's ||W - 1 w_bar||_F^2)
        w_new_flat = _flatten_stack(w_new)
        consensus_err = jnp.sum((w_new_flat - w_new_flat.mean(0)) ** 2)

        if dyn:
            # budget debit: each realized broadcast ships one model payload
            n_bytes = float(accounting.model_bytes(model_dim))
            res_new = resources_mod.ResourceState(
                bw=bw_live, budget=res.budget - n_bytes * v.astype(jnp.float32),
                up=up, key=r_key)
            down_count = jnp.sum(~up).astype(jnp.int32)
            exhausted_count = jnp.sum(exhausted).astype(jnp.int32)
        else:
            res_new = state.resources
            down_count = jnp.zeros((), jnp.int32)
            exhausted_count = jnp.zeros((), jnp.int32)

        if fdyn:
            f_new = faults_mod.FaultState(crashed=crashed, staleness=staleness,
                                          cluster_down=cluster_down, key=f_key)
            fault_down_count = jnp.sum(~f_up).astype(jnp.int32)
            stale_max = jnp.max(staleness)
        else:
            f_new = state.faults
            fault_down_count = jnp.zeros((), jnp.int32)
            stale_max = jnp.zeros((), jnp.int32)

    new_state = EFHCState(
        w=w_new, w_hat=w_hat_new, k=state.k + 1, prev_adj=prev_adj_next,
        bandwidths=state.bandwidths, key=key, opt_state=opt_state_new,
        resources=res_new, faults=f_new, watchdog=wd_new,
    )
    return new_state, StepAux(v=v, comm=comm, p=p, loss=loss, tx_time=tx_time,
                              util=util, adj=adj, consensus_err=consensus_err,
                              comm_count=used_i, deg=deg_i,
                              down_count=down_count,
                              exhausted_count=exhausted_count,
                              fault_down_count=fault_down_count,
                              stale_max=stale_max,
                              window_connected=window_connected,
                              window_needed=window_needed)


# ---------------------------------------------------------------------------
# Sharded fleet step: one shard's slice of Alg. 1 inside shard_map over the
# 1-D "fl" mesh axis (DESIGN.md "Sharded fleet engine").  Cross-shard state
# moves through one halo exchange of only the boundary rows; everything
# else is the exact per-row arithmetic of ``step``'s sparse branch, so the
# owned-device trajectories stay bit-identical to the single-device engine.
# ---------------------------------------------------------------------------

class ShardCtx(NamedTuple):
    """One shard's slice of a ``topology.ShardPlan``, as traced arrays."""

    owned: jax.Array  # (ms,) global device ids
    nbr_gid: jax.Array  # (ms, d_max) global neighbor ids
    nbr_loc: jax.Array  # (ms, d_max) index into the [own; halo] buffer
    mask: jax.Array  # (ms, d_max) real-slot mask
    send_idx: jax.Array  # (B_max,) local boundary rows
    recv_src: jax.Array  # (H_max,) flat positions in the gathered buffer


class ShardAux(NamedTuple):
    """Per-iteration outputs of one shard: the summary-trace channels of
    ``StepAux`` -- per-device vectors stay shard-local (the engine gathers
    them into global order once, outside the scan), scalars are already
    fleet-global (identical on every shard)."""

    v: jax.Array  # (ms,) broadcast events fired
    loss: jax.Array  # (ms,) per-device minibatch loss
    tx_time: jax.Array  # scalar, replicated
    util: jax.Array  # scalar, replicated
    consensus_err: jax.Array  # scalar, replicated (hierarchical fp32 sum)
    comm_count: jax.Array  # (ms,) int32
    deg: jax.Array  # (ms,) int32
    # fleet-global resource counters (psum'd, replicated; zeros if disabled)
    down_count: jax.Array  # scalar int32
    exhausted_count: jax.Array  # scalar int32
    # fleet-global fault counters (psum/pmax'd, replicated)
    fault_down_count: jax.Array  # scalar int32
    stale_max: jax.Array  # scalar int32
    # watchdog channels (pmax'd inside the watchdog, replicated)
    window_connected: jax.Array  # scalar bool
    window_needed: jax.Array  # scalar int32


def halo_exchange(ctx: ShardCtx, axis_name: str, x: jax.Array) -> jax.Array:
    """(ms, ...) per-row payload -> (H_max, ...) halo rows: all-gather only
    the boundary rows (``send_idx``) and pick this shard's halo out of the
    flat (S * B_max, ...) result at ``recv_src``.  Pad slots carry row
    0 / position 0 junk; every consumer masks or zero-weights them."""
    with jax.named_scope("efhc.halo"):
        gath = jax.lax.all_gather(x[ctx.send_idx], axis_name)
        return gath.reshape((-1,) + gath.shape[2:])[ctx.recv_src]


def step_sharded(
    cfg: EFHCConfig,
    graph: GraphProcess,
    ctx: ShardCtx,
    state: EFHCState,
    *,
    grad_fn: Callable[[Any, jax.Array, Any], tuple[jax.Array, Any]],
    batch,
    alpha_k: jax.Array,
    model_dim: int,
    m: int,
    inv_perm: jax.Array,
    axis_name: str = "fl",
    policy_idx: jax.Array | None = None,
    opt_update: Callable[[Any, Any, Any, jax.Array], tuple[Any, Any]] | None = None,
    ftabs: faults_mod.FaultTabs | None = None,
) -> tuple[EFHCState, ShardAux]:
    """One universal iteration of Alg. 1 for this shard's ``ms`` devices.

    ``state`` holds the *local* slices (w/w_hat leaves (ms, ...), bandwidths
    (ms,), prev_adj the (ms, d_max) ELL mask) except ``key``, which is the
    fleet-global key replicated on every shard so the split stream matches
    the single-device engine.  ``batch`` is the shard's (ms, ...) slice.

    Bit-exactness vs ``step`` (mix_impl="sparse"), per DESIGN.md:
      * graph realization: ``adjacency_ell_rows`` draws per-edge randomness
        by canonical global edge id -- any row subset sees the same draw;
      * triggers: thresholds are elementwise; gossip realizes the full (m,)
        draw and slices owned rows (``triggers.policy_branches_rows``);
      * mixing: the halo gather buffer holds bit-identical row values and
        ``mix_sparse_halo`` runs the same slot-loop accumulation order;
      * SGD: per-device grad keys are ``split(k_grad, m)[owned]``;
      * tx_time/util: per-device terms are gathered back into *global*
        device order (``inv_perm``) and reduced with the same expressions.
    The one deliberate exception is ``consensus_err``: reconstructing the
    (m, n) stack per iteration would defeat the partitioning, so it is a
    hierarchical psum (mean via column psum, then a psum of local squared
    deviations) -- equal to the single-device value up to fp32 summation
    order, and tested with tolerance, never bit-compared.

    Named scopes as in ``step``, plus ``efhc.halo`` around every
    ``halo_exchange`` (innermost, also where an Event's masks call it)."""
    ms = state.bandwidths.shape[0]
    ex = lambda x: halo_exchange(ctx, axis_name, x)
    with jax.named_scope("efhc.event1"):
        key, k_trig, k_grad = jax.random.split(state.key, 3)

        # resource dynamics: the same Python-level gate as ``step``; draws are
        # positional (m,) sliced by ``ctx.owned`` so every shard count realizes
        # the identical per-device stream (DESIGN.md "Resource dynamics")
        rcfg = cfg.resources
        dyn = rcfg is not None and rcfg.enabled
        if dyn:
            res = state.resources
            r_key, k_evolve = jax.random.split(res.key)
            up, straggle, bw_live = resources_mod.evolve(
                rcfg, k_evolve, res.up, res.bw, state.bandwidths, m,
                rows=ctx.owned)
            exhausted = resources_mod.exhausted_mask(rcfg, res.budget)
            bw_thresh = jnp.where(
                exhausted, resources_mod.EXHAUSTED_BW_FRAC * state.bandwidths,
                bw_live)
        else:
            bw_thresh = state.bandwidths
            bw_live = state.bandwidths

        # correlated faults: per-device draws are positional (m,) sliced by
        # ``ctx.owned``; cluster bits evolve from the replicated global key, so
        # every shard realizes the identical outage pattern
        fcfg = cfg.faults
        fdyn = fcfg is not None and fcfg.enabled
        if fdyn:
            fstate = state.faults
            f_key, k_fevolve = jax.random.split(fstate.key)
            crashed, rejoined, staleness, cluster_down = faults_mod.evolve(
                fcfg, k_fevolve, fstate.crashed, fstate.staleness,
                fstate.cluster_down, m, rows=ctx.owned)
            f_up = faults_mod.device_up(crashed, cluster_down, ftabs.labels)

        wcfg = cfg.watchdog
        wdog = wcfg is not None and wcfg.enabled

        adj_ell = graph.adjacency_ell_rows(state.k, ctx.nbr_gid, ctx.mask, ctx.owned)
        if dyn:
            # churn masks Events 1-3; neighbor liveness arrives over the halo
            # (pad slots carry junk up-bits, but adj_ell is already False there)
            up_buf = jnp.concatenate([up, ex(up)])
            adj_ell = jnp.logical_and(
                adj_ell, jnp.logical_and(up[:, None], up_buf[ctx.nbr_loc]))
        if fdyn:
            f_up_buf = jnp.concatenate([f_up, ex(f_up)])
            adj_ell = jnp.logical_and(
                adj_ell, jnp.logical_and(f_up[:, None], f_up_buf[ctx.nbr_loc]))
            if fcfg.edge_faults:
                # edge tables are keyed by canonical global edge id, so the
                # shard's rows see the identical (k, edge) schedule
                adj_ell = jnp.logical_and(
                    adj_ell, faults_mod.edge_keep(fcfg, state.k, ftabs))
        deg_i = adj_ell.sum(axis=1, dtype=jnp.int32)

    # ---- Event 2: broadcast triggers (local rows) ------------------------
    with jax.named_scope("efhc.event2"):
        w_flat = _flatten_stack(state.w)
        w_hat_flat = _flatten_stack(state.w_hat)
        gamma_k = cfg.gamma(state.k) if cfg.gamma is not None else alpha_k
        dev = triggers.rms_deviation(w_flat, w_hat_flat)
        branches = triggers.policy_branches_rows(cfg.trigger, m, ctx.owned)
        if policy_idx is None:
            v = branches[triggers.policy_index(cfg.trigger.policy)](
                dev, bw_thresh, gamma_k, k_trig)
        else:
            v = jax.lax.switch(policy_idx, branches,
                               dev, bw_thresh, gamma_k, k_trig)
        if dyn:
            # hard mask before the halo ships v: down / exhausted devices fire
            # nothing, and their neighbors must agree
            v = jnp.logical_and(v, jnp.logical_and(up, ~exhausted))
        if fdyn:
            v = jnp.logical_and(v, f_up)

    # ---- halo exchange: boundary rows of (w_flat, v, deg) ----------------
    # the halo ships the canonical (ms, D) flat rows -- one gathered array
    # regardless of how many leaves the model pytree has
    with jax.named_scope("efhc.halo"):
        w_halo_flat = ex(w_flat)
        v_buf = jnp.concatenate([v, ex(v)])
        deg_buf = jnp.concatenate([deg_i, ex(deg_i)])

    # ---- Events 1 + 3: new links, information-flow edges, mixing ---------
    with jax.named_scope("efhc.event1"):
        new_links_ell = jnp.logical_and(adj_ell, ~state.prev_adj)
    with jax.named_scope("efhc.event3"):
        vv_ell = jnp.logical_or(v[:, None], v_buf[ctx.nbr_loc])
        comm_ell = jnp.logical_or(jnp.logical_and(vv_ell, adj_ell), new_links_ell)
        p_diag, p_off = mixing.build_p_ell_halo(ctx.nbr_loc, adj_ell, comm_ell,
                                                deg_buf)
        w_mixed_flat = consensus.mix_sparse_halo(ctx.nbr_loc, p_diag, p_off,
                                                 w_flat, w_halo_flat)
        used_i = comm_ell.sum(axis=1, dtype=jnp.int32)

        if fdyn and fcfg.warm_start:
            # staleness-aware rejoin: neighbor values come out of the [own;
            # halo] buffer of *pre-patch* rows -- the identical slot-order sum
            # the single-device sparse impl performs, so owned-row trajectories
            # stay bit-exact
            w_buf = jnp.concatenate([w_flat, w_halo_flat])
            nb_sum = jnp.where(adj_ell[..., None], w_buf[ctx.nbr_loc], 0.0
                               ).sum(axis=1)
            nb_cnt = adj_ell.sum(axis=1, dtype=jnp.float32)
            nb_avg = nb_sum / jnp.maximum(nb_cnt, 1.0)[:, None]
            patch = jnp.logical_and(rejoined, nb_cnt > 0)
            w_mixed_flat = jnp.where(patch[:, None], nb_avg, w_mixed_flat)

        if wdog:
            wd_age, window_connected, window_needed = flow_mod.watchdog_step_halo(
                wcfg, m, ctx.nbr_loc, ctx.owned, comm_ell, state.watchdog.age,
                ex, axis_name)
            wd_new = flow_mod.WatchdogState(age=wd_age)
        else:
            wd_new = state.watchdog
            window_connected = jnp.ones((), bool)
            window_needed = jnp.zeros((), jnp.int32)

        def upd_hat(h, wcur):
            mask = v.reshape((ms,) + (1,) * (wcur.ndim - 1))
            return jnp.where(mask, wcur, h)

        w_hat_new = jax.tree.map(upd_hat, state.w_hat, state.w)

    # ---- Event 4: local SGD (global per-device key stream, sliced) -------
    with jax.named_scope("efhc.event4"):
        w_mixed = unflatten_stack(w_mixed_flat, state.w)
        grad_keys = jax.random.split(k_grad, m)[ctx.owned]
        loss, grads = jax.vmap(grad_fn, in_axes=(0, 0, 0))(w_mixed, grad_keys, batch)
        if opt_update is None:
            w_new = jax.tree.map(
                lambda wm, g: (wm.astype(jnp.float32)
                               - alpha_k * g.astype(jnp.float32)).astype(wm.dtype),
                w_mixed, grads)
            opt_state_new = state.opt_state
        else:
            w_new, opt_state_new = opt_update(grads, state.opt_state, w_mixed,
                                              alpha_k)
        if dyn or fdyn:
            upd = None
            if dyn:
                upd = jnp.logical_and(up, ~straggle)
            if fdyn:
                upd = f_up if upd is None else jnp.logical_and(upd, f_up)
            w_new = _mask_update_rows(upd, ms, w_new, w_mixed)
            opt_state_new = _mask_update_rows(upd, ms, opt_state_new,
                                              state.opt_state)

    # ---- paper metrics: reduce in single-device order --------------------
    with jax.named_scope("efhc.stats"):
        def global_order(x_local):
            # (ms,) -> (m,) in *global* device order: the all-gather lands in
            # shard-major (permuted) order, inv_perm maps device id -> position
            return jax.lax.all_gather(x_local, axis_name).reshape(-1)[inv_perm]

        deg = deg_i.astype(jnp.float32)
        used = used_i.astype(jnp.float32)
        frac = jnp.where(deg > 0, used / jnp.maximum(deg, 1.0), 0.0)
        tx_time = jnp.mean(global_order(frac * model_dim / bw_live))
        capacity = jnp.sum(global_order(deg * bw_live))
        util = (jnp.sum(global_order(used * model_dim))
                / jnp.maximum(capacity, 1e-12))

        w_new_flat = _flatten_stack(w_new)
        col_mean = jax.lax.psum(w_new_flat.sum(axis=0), axis_name) / m
        consensus_err = jax.lax.psum(jnp.sum((w_new_flat - col_mean) ** 2),
                                     axis_name)

        if dyn:
            n_bytes = float(accounting.model_bytes(model_dim))
            res_new = resources_mod.ResourceState(
                bw=bw_live, budget=res.budget - n_bytes * v.astype(jnp.float32),
                up=up, key=r_key)
            down_count = jax.lax.psum(jnp.sum(~up).astype(jnp.int32), axis_name)
            exhausted_count = jax.lax.psum(
                jnp.sum(exhausted).astype(jnp.int32), axis_name)
        else:
            res_new = state.resources
            down_count = jnp.zeros((), jnp.int32)
            exhausted_count = jnp.zeros((), jnp.int32)

        if fdyn:
            f_new = faults_mod.FaultState(crashed=crashed, staleness=staleness,
                                          cluster_down=cluster_down, key=f_key)
            fault_down_count = jax.lax.psum(jnp.sum(~f_up).astype(jnp.int32),
                                            axis_name)
            stale_max = jax.lax.pmax(jnp.max(staleness), axis_name)
        else:
            f_new = state.faults
            fault_down_count = jnp.zeros((), jnp.int32)
            stale_max = jnp.zeros((), jnp.int32)

    new_state = EFHCState(
        w=w_new, w_hat=w_hat_new, k=state.k + 1, prev_adj=adj_ell,
        bandwidths=state.bandwidths, key=key, opt_state=opt_state_new,
        resources=res_new, faults=f_new, watchdog=wd_new,
    )
    return new_state, ShardAux(v=v, loss=loss, tx_time=tx_time, util=util,
                               consensus_err=consensus_err,
                               comm_count=used_i, deg=deg_i,
                               down_count=down_count,
                               exhausted_count=exhausted_count,
                               fault_down_count=fault_down_count,
                               stale_max=stale_max,
                               window_connected=window_connected,
                               window_needed=window_needed)
