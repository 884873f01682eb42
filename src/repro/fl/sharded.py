"""Sharded fleet engine: the chunked-scan simulation partitioned across a
1-D ``fl`` device mesh (DESIGN.md "Sharded fleet engine").

``make_engine`` (fl/simulator.py) holds the whole fleet on one device --
the m >= 10^5 regime the paper's D2D setting targets blows past a single
device's memory on the ELL mixing state and the scan ys.  Here the fleet is
partitioned by ``topology.shard_plan``: each shard owns ``ms = m / S``
device rows (theta, neighbor lists, trigger state) and runs Events 1/2/3/4
locally via ``efhc.step_sharded``; cross-shard neighbor rows arrive through
one halo exchange of only the *boundary* rows per iteration.  The entire
chunked ``lax.scan`` runs inside ``shard_map``, so per-iteration collectives
compile into the one program and the ys stay sharded until the final
device_get.

The engine keeps the single-device trajectory bit-exactly (m <= 512
acceptance, ``tests/test_sharded.py``): graph realization, triggers, mixing
order, and grad-key streams are all global-id-keyed, and fleet scalars are
reduced in global device order -- see ``efhc.step_sharded`` for the
per-mechanism accounting.  ``consensus_err`` alone is hierarchical (fp32
summation-order tolerance).

Trace mode is ``summary`` only: full/packed link matrices are (m, m)-sized,
exactly what sharding exists to avoid materializing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import efhc, topology, triggers
from repro.core import faults as faults_mod
from repro.core import flow as flow_mod
from repro.core import resources as resources_mod
from repro.core.topology import GraphProcess
from repro.fl import trace as trace_mod
from repro.launch.mesh import make_fleet_mesh
from repro.optim.optimizers import init_opt
from repro.optim.schedules import paper_diminishing

_AXIS = "fl"


def make_sharded_engine(
    sim,
    graph: GraphProcess,
    *,
    T: int,
    eval_every: int = 10,
    x: np.ndarray,
    y: np.ndarray,
    eval_fn=None,
    n_shards: int | None = None,
):
    """Builds the sharded simulation engine: the same pure-function contract
    as ``simulator.make_engine`` --

        engine(policy_idx, seed, idx) -> dict of full trajectories

    with outputs already reassembled into *global* device order, so
    ``simulator.run`` consumes either engine interchangeably.  ``n_shards``
    defaults to ``sim.shards``; the fleet mesh needs that many jax devices
    (forced host devices on CPU, see ``launch.mesh.make_fleet_mesh``).
    """
    from repro.fl import simulator  # deferred: simulator routes to us

    E = max(1, int(eval_every))
    m = sim.m
    S = int(sim.shards if n_shards is None else n_shards)
    if trace_mod.check_trace_mode(sim.trace) != "summary":
        raise ValueError(
            f"the sharded engine records summary traces only (per-device "
            f"counts); got trace={sim.trace!r} -- full/packed link matrices "
            "are the (m, m) state sharding exists to avoid")
    if eval_fn is not None and not isinstance(eval_fn, simulator.EvalFn):
        raise ValueError(
            "the sharded engine folds evaluation into the compiled program; "
            "pass an EvalFn (or None), not a host callable")

    plan = topology.shard_plan(graph.edges, S, coords=graph.coords)
    mesh = make_fleet_mesh(S)
    P = jax.sharding.PartitionSpec

    spec = simulator.model_spec(sim)
    grad_fn = spec.grad_fn
    logits_fn = spec.eval_logits
    opt = init_opt(sim.optimizer)
    cfg = simulator._efhc_cfg(sim)
    sched = paper_diminishing(sim.alpha0, gamma=1.0, theta=0.5)
    model_dim = spec.flat_dim
    x_all, y_all = jnp.asarray(x), jnp.asarray(y)
    if eval_fn is not None:
        x_test, y_test = eval_fn.x_test, eval_fn.y_test

    # the plan's per-shard tables, stacked (S, ...) and split over the mesh
    tables = (plan.owned, plan.nbr_gid, plan.nbr_loc, plan.mask,
              plan.send_idx, plan.recv_src)
    n_ctx = len(tables)
    perm_flat = plan.owned.reshape(-1)  # shard-major device order
    inv_perm = jnp.asarray(plan.inv_perm)

    rcfg = cfg.resources
    fcfg = cfg.faults
    wcfg = cfg.watchdog
    if fcfg is not None:
        # per-shard fault tables in the shard's own ELL row layout, stacked
        # (S, ms, d_max) like the plan tables; keyed by canonical global
        # edge id, so each shard sees the identical per-edge marks
        fab = faults_mod.fault_fabric(graph, fcfg)
        per_shard = [faults_mod.edge_tables_rows(
                         fab, graph.edges, plan.nbr_gid[s], plan.mask[s],
                         rows=plan.owned[s]) for s in range(S)]
        tables = tables + tuple(
            np.stack([np.asarray(t[i]) for t in per_shard])
            for i in range(len(faults_mod.FaultTabs._fields)))
    else:
        fab = None

    def shard_body(policy_idx, k_bw, k_init, k_state, k_res, k_fault, alphas,
                   idx_sh, *tabs):
        ctx = efhc.ShardCtx(*(t[0] for t in tabs[:n_ctx]))  # drop shard dim
        ftabs = (faults_mod.FaultTabs(*(t[0] for t in tabs[n_ctx:]))
                 if fcfg is not None else None)

        def global_order(x_local):
            return jax.lax.all_gather(x_local, _AXIS).reshape(-1)[inv_perm]

        with jax.named_scope("efhc.init"):
            # fleet-global RNG streams, sliced to the owned rows: identical
            # per-device values at every shard count
            bw = triggers.sample_bandwidths(k_bw, m, sim.b_mean, sim.sigma_n)
            bw_l = bw[ctx.owned]
            w0 = spec.init_rows(k_init, m, ctx.owned)
            adj0 = graph.adjacency_ell_rows(0, ctx.nbr_gid, ctx.mask, ctx.owned)
            # resource state: local rows, fleet-global stream key (replicated)
            res0 = (resources_mod.init_state(rcfg, bw_l, k_res)
                    if rcfg is not None else None)
            # fault state: local crash/staleness rows, fleet-global cluster
            # bits + stream key (replicated on every shard)
            f0 = (faults_mod.init_state(fcfg, fab, k_fault, rows=ctx.owned)
                  if fcfg is not None else None)
            wd0 = (flow_mod.watchdog_init(ctx.nbr_loc.shape[0],
                                          ctx.nbr_loc.shape[1])
                   if wcfg is not None else None)
            state = efhc.init_state(w0, bw_l, adj0, k_state,
                                    opt_state=opt.init(w0), resources=res0,
                                    faults=f0, watchdog=wd0)

        def one_step(st, per):
            ix, alpha = per  # ix: (ms, batch) dataset rows
            with jax.named_scope("efhc.event4"):
                batch = (x_all[ix], y_all[ix])
            st, aux = efhc.step_sharded(
                cfg, graph, ctx, st, grad_fn=grad_fn, batch=batch,
                alpha_k=alpha, model_dim=model_dim, m=m, inv_perm=inv_perm,
                axis_name=_AXIS, policy_idx=policy_idx, opt_update=opt.update,
                ftabs=ftabs)
            return st, aux._asdict()

        def eval_acc(st):
            if eval_fn is None:
                return jnp.asarray(0.0, jnp.float32)

            def one(w):
                return (logits_fn(w, x_test).argmax(-1) == y_test).mean()

            # per-device accuracies, reduced in global order: matches the
            # single-device EvalFn.device (vmap + mean over all m)
            with jax.named_scope("efhc.eval"):
                return jnp.mean(global_order(jax.vmap(one)(st.w))).astype(
                    jnp.float32)

        def chunk_body(st, chunk):
            st, aux0 = one_step(st, jax.tree.map(lambda a: a[0], chunk))
            acc = eval_acc(st)
            st, auxr = jax.lax.scan(one_step, st,
                                    jax.tree.map(lambda a: a[1:], chunk))
            aux = jax.tree.map(lambda a, b: jnp.concatenate([a[None], b], 0),
                               aux0, auxr)
            return st, (aux, acc)

        per = (idx_sh, alphas)
        n_full, rem = divmod(T, E)
        head = jax.tree.map(
            lambda a: a[: n_full * E].reshape((n_full, E) + a.shape[1:]), per)
        state, (aux_h, accs) = jax.lax.scan(chunk_body, state, head)
        aux = jax.tree.map(lambda a: a.reshape((n_full * E,) + a.shape[2:]),
                           aux_h)
        acc_t = jnp.repeat(accs, E, total_repeat_length=n_full * E)
        if rem:
            tail = jax.tree.map(lambda a: a[n_full * E:], per)
            state, (aux_r, acc_r) = chunk_body(state, tail)
            aux = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                               aux, aux_r)
            acc_t = jnp.concatenate([acc_t, jnp.full((rem,), acc_r)])
        acc_t = acc_t.at[T - 1].set(eval_acc(state))

        return {**aux, "acc": acc_t, "bandwidths": bw_l}

    dev_spec = P(None, _AXIS)  # (T, m) per-device channels, sharded on m
    out_specs = {"v": dev_spec, "loss": dev_spec, "comm_count": dev_spec,
                 "deg": dev_spec, "tx_time": P(), "util": P(),
                 "consensus_err": P(), "acc": P(), "bandwidths": P(_AXIS),
                 "down_count": P(), "exhausted_count": P(),
                 "fault_down_count": P(), "stale_max": P(),
                 "window_connected": P(), "window_needed": P()}
    in_specs = ((P(), P(), P(), P(), P(), P(), P(), P(None, _AXIS, None))
                + (P(_AXIS),) * len(tables))
    mapped = jax.shard_map(shard_body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)

    def engine(policy_idx, seed, idx):
        policy_idx = jnp.asarray(policy_idx, jnp.int32)
        key = jax.random.PRNGKey(seed)
        k_bw, k_init, k_state = jax.random.split(key, 3)
        k_res = (resources_mod.resource_key(key, rcfg)
                 if rcfg is not None else k_state)
        k_fault = (faults_mod.fault_key(key, fcfg)
                   if fcfg is not None else k_state)
        alphas = sched(jnp.arange(T))
        idx_p = jnp.asarray(idx)[:, perm_flat]  # shard-major rows
        out = mapped(policy_idx, k_bw, k_init, k_state, k_res, k_fault,
                     alphas, idx_p, *[jnp.asarray(t) for t in tables])
        # per-device channels come back in shard-major order; restore the
        # global device order the SimResult contract promises
        for f in ("v", "loss", "comm_count", "deg"):
            out[f] = out[f][:, inv_perm]
        out["bandwidths"] = out["bandwidths"][inv_perm]
        return out

    return engine, model_dim, plan
