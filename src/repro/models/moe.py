"""Mixture-of-Experts FFN.

Two implementations:

* ``dispatch`` — GShard-style capacity-based dispatch/combine einsums.  The
  expert axis of the intermediate tensors is sharded over the ``model`` mesh
  axis (expert parallelism); XLA inserts the all-to-all at the resharding
  boundary.  Used by the full-size configs / dry-run.
* ``dense`` — every expert computed for every token, then weighted-combined.
  O(E x) flops; only for tiny smoke configs and as the test oracle.

Router: softmax over expert logits, top-k selection, probs renormalized over
the selected experts (deepseek/granite style), plus the standard
load-balancing auxiliary loss (Switch/GShard).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig, MoEConfig
from repro.models.layers import act_fn, dense_init


def init_moe(cfg: ArchConfig, key, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 7)
    gated = cfg.act in ("swiglu", "geglu")
    p = {
        "router": dense_init(ks[0], (d, m.n_experts), d, jnp.float32),
        "w_in": dense_init(ks[1], (m.n_experts, d, m.d_expert), d, dtype),
        "w_out": dense_init(ks[2], (m.n_experts, m.d_expert, d), m.d_expert, dtype),
    }
    if gated:
        p["w_gate"] = dense_init(ks[3], (m.n_experts, d, m.d_expert), d, dtype)
    if m.n_shared > 0:
        p["shared_in"] = dense_init(ks[4], (d, m.n_shared * m.d_expert), d, dtype)
        p["shared_out"] = dense_init(ks[5], (m.n_shared * m.d_expert, d), m.n_shared * m.d_expert, dtype)
        if gated:
            p["shared_gate"] = dense_init(ks[6], (d, m.n_shared * m.d_expert), d, dtype)
    return p


def router_probs(m: MoEConfig, p, x) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (combine weights (..., E) sparse, top-k indices, aux loss)."""
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, m.top_k)
    top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss: E * sum_e f_e * p_e  (Switch, eq. 4)
    e = m.n_experts
    me = probs.mean(axis=tuple(range(probs.ndim - 1)))  # avg router prob per expert
    onehot = jax.nn.one_hot(top_idx[..., 0], e, dtype=jnp.float32)  # top-1 assignment share
    ce = onehot.mean(axis=tuple(range(onehot.ndim - 1)))
    aux = e * jnp.sum(me * ce)
    return top_vals, top_idx, aux


def _expert_ffn(cfg: ArchConfig, p, x_e):
    """x_e: (E, C*, d) per-expert token slabs -> (E, C*, d)."""
    h = jnp.einsum("ecd,edf->ecf", x_e, p["w_in"])
    if "w_gate" in p:
        h = act_fn(cfg.act, jnp.einsum("ecd,edf->ecf", x_e, p["w_gate"])) * h
    else:
        h = act_fn(cfg.act, h)
    return jnp.einsum("ecf,efd->ecd", h, p["w_out"])


def _shared_ffn(cfg: ArchConfig, p, x):
    h = x @ p["shared_in"]
    if "shared_gate" in p:
        h = act_fn(cfg.act, x @ p["shared_gate"]) * h
    else:
        h = act_fn(cfg.act, h)
    return h @ p["shared_out"]


def moe_ffn(cfg: ArchConfig, p, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (out, aux_loss)."""
    m = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    top_vals, top_idx, aux = router_probs(m, p, xt)

    if m.impl == "shard_map":
        from repro.models.sharding import _ACT_CTX

        mesh = getattr(_ACT_CTX, "mesh", None)
        if mesh is not None and "model" in mesh.axis_names:
            mapping = getattr(_ACT_CTX, "mapping", {}) or {}
            fsdp = mapping.get("batch") == "data"
            out = _shard_map_moe(cfg, p, xt, mesh, fsdp=fsdp)
            if m.n_shared > 0:
                out = out + _shared_ffn(cfg, p, xt)
            out = out.reshape(b, s, d)
            from repro.models.sharding import constrain

            out = constrain(out, ("batch", "seq", "embed"))
            return out, aux
        # no mesh context (unit tests): fall through to scatter
        out = _scatter_moe(cfg, p, xt, top_vals, top_idx)
    elif m.impl == "dense":
        # oracle: all experts on all tokens
        all_out = _expert_ffn(cfg, p, jnp.broadcast_to(xt[None], (m.n_experts, b * s, d)))
        combine = jnp.zeros((b * s, m.n_experts), jnp.float32)
        combine = jax.vmap(lambda c, i, v: c.at[i].add(v))(combine, top_idx, top_vals)
        out = jnp.einsum("te,etd->td", combine.astype(x.dtype), all_out)
    elif m.impl == "scatter":
        out = _scatter_moe(cfg, p, xt, top_vals, top_idx)
    else:
        out = _dispatch_moe(cfg, p, xt, top_vals, top_idx)
    if m.n_shared > 0:
        out = out + _shared_ffn(cfg, p, xt)
    out = out.reshape(b, s, d)
    # sequence-parallel output: lets XLA turn the expert-combine reduction
    # over the model axis into a reduce-scatter into seq shards
    from repro.models.sharding import constrain

    out = constrain(out, ("batch", "seq", "embed"))
    return out, aux


def _dispatch_group_count(t: int, target: int = 8192) -> int:
    """Largest divisor of t not exceeding max(t // target, 1)."""
    want = max(t // target, 1)
    g = 1
    for cand in range(1, want + 1):
        if t % cand == 0:
            g = cand
    return g


def _scatter_moe(cfg: ArchConfig, p, xt, top_vals, top_idx):
    """Grouped scatter/gather expert dispatch.

    Tokens are split into G groups of Tg (= per-shard granularity); each
    group scatters its tokens into its own (E, Cg, d) expert buffer with
    per-group capacity Cg = Tg*K*cf/E.  The group axis shards over `data`
    and the expert axis over `model`, so the scatter stays shard-local and
    the G-sharded -> E-sharded reshard at the expert-FFN boundary is the
    canonical MoE all-to-all.  This replaces (a) the GShard (T, E, C)
    one-hot einsum (O(T * Tg * k * cf) memory, measured ~100 GB/device) and
    (b) the ungrouped scatter whose capacity scaled with the full replica
    token count (~19 GB f32 buffers all-reduced across `data`); see
    EXPERIMENTS.md §Perf."""
    from repro.models.sharding import constrain

    m = cfg.moe
    t, d = xt.shape
    from repro import variants as _v

    g = _dispatch_group_count(t, target=int(_v.value("moe_groups", 8192)))
    tg = t // g
    cap = max(int(tg * m.top_k * m.capacity_factor / m.n_experts), 4)

    xg = xt.reshape(g, tg, d)
    idxg = top_idx.reshape(g, tg, m.top_k)
    valg = top_vals.reshape(g, tg, m.top_k)
    onehot = jax.nn.one_hot(idxg, m.n_experts, dtype=jnp.int32)  # (G,Tg,K,E)
    flat = onehot.reshape(g, tg * m.top_k, m.n_experts)
    flat = constrain(flat, ("batch", None, "expert"))
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(g, tg, m.top_k, m.n_experts)
    pos = (pos * onehot).sum(-1)  # (G,Tg,K) queue slot within (group, expert)
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)  # overflow -> dropped slot Cg

    def scatter_group(xg_i, idx_i, slot_i):
        buf = jnp.zeros((m.n_experts, cap + 1, d), xt.dtype)
        for k in range(m.top_k):
            buf = buf.at[idx_i[:, k], slot_i[:, k]].add(xg_i)
        return buf

    x_e = jax.vmap(scatter_group)(xg, idxg, slot)  # (G,E,Cg+1,d)
    x_e = constrain(x_e, ("batch", "expert", None, "embed"))
    y_e = _expert_ffn_grouped(cfg, p, x_e[:, :, :cap])
    y_e = constrain(y_e, ("batch", "expert", None, "embed"))
    y_e = jnp.pad(y_e, ((0, 0), (0, 0), (0, 1), (0, 0)))  # dropped slot -> 0

    from repro import variants

    acc_dt = xt.dtype if variants.active("moe_bf16") else jnp.float32

    def gather_group(ye_i, idx_i, slot_i, val_i, keep_i):
        # accumulation dtype controls the dtype of the cross-(expert-shard)
        # combine reduction XLA emits: f32 is the safe default, bf16 halves
        # the collective bytes (variant `moe_bf16`, §Perf)
        out = jnp.zeros((tg, d), acc_dt)
        for k in range(m.top_k):
            gk = (val_i[:, k] * keep_i[:, k]).astype(acc_dt)
            out = out + gk[:, None] * ye_i[idx_i[:, k], slot_i[:, k]].astype(acc_dt)
        return out.astype(xt.dtype)

    out = jax.vmap(gather_group)(y_e, idxg, slot, valg, keep)
    return out.reshape(t, d)


def _shard_map_moe(cfg: ArchConfig, p, xt, mesh, *, fsdp: bool = True):
    """Explicit expert parallelism under shard_map (beyond-paper, §Perf
    hillclimb 1).  Topology: experts shard over `model`; tokens shard over
    `data` and are replicated across `model`, so every (data, model) device
    processes its data-row's tokens through its own expert shard *locally*
    (masked scatter -> FFN -> masked gather) and the combine is a single
    bf16 psum-scatter over `model` - replacing the O(50x) f32 masked-partial
    all-reduces XLA's SPMD partitioner emits for the gather/scatter form.

    In replica mode (m = fl_m model replicas under vmap) only the `model`
    axis is manually partitioned (`axis_names={"model"}`); the fl axes stay
    automatic so the vmap(spmd_axis_name=...) sharding composes.
    """
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    t, d = xt.shape
    n_model = mesh.shape["model"]
    e_local = m.n_experts // n_model
    axes = tuple(mesh.axis_names)

    def full(*dims):
        return P(*dims, *([None] * 0))

    gated = "w_gate" in p
    w_names = ["router", "w_in", "w_out"] + (["w_gate"] if gated else [])
    weights = {k: p[k] for k in w_names}
    if fsdp:
        w_specs = {
            "router": P(None, None),
            "w_in": P("model", "data", None),
            "w_out": P("model", None, "data"),
            **({"w_gate": P("model", "data", None)} if gated else {}),
        }
        # x: tokens over data, replicated over model (and pod, if present)
        x_spec = P("data", None)
        out_spec = P(("data", "model"), None)
        manual = frozenset(mesh.axis_names)
        tl = t // mesh.shape["data"]
    else:
        # replica mode (runs under vmap(spmd_axis_name=fl axes)): manual
        # partitioning over `model` only; the fl axes stay automatic
        w_specs = {
            "router": P(None, None),
            "w_in": P("model", None, None),
            "w_out": P("model", None, None),
            **({"w_gate": P("model", None, None)} if gated else {}),
        }
        x_spec = P(None, None)
        out_spec = P("model", None)
        manual = frozenset({"model"})
        tl = t

    cap = max(int(tl * m.top_k * m.capacity_factor / m.n_experts), 4)

    def local(x_l, w):
        mi = jax.lax.axis_index("model")
        if fsdp:
            # fsdp gather of this shard's expert weights over `data`
            w_in = jax.lax.all_gather(w["w_in"], "data", axis=1, tiled=True)
            w_out = jax.lax.all_gather(w["w_out"], "data", axis=2, tiled=True)
            w_gate = (jax.lax.all_gather(w["w_gate"], "data", axis=1, tiled=True)
                      if gated else None)
        else:
            w_in, w_out = w["w_in"], w["w_out"]
            w_gate = w["w_gate"] if gated else None
        tl = x_l.shape[0]
        logits = x_l.astype(jnp.float32) @ w["router"]
        top_vals, top_idx, _ = _topk_renorm(m, logits)
        # queue slot within each (global) expert, computed over local tokens
        onehot = jax.nn.one_hot(top_idx, m.n_experts, dtype=jnp.int32)
        flat = onehot.reshape(tl * m.top_k, m.n_experts)
        pos = (jnp.cumsum(flat, axis=0) - flat).reshape(tl, m.top_k, m.n_experts)
        pos = (pos * onehot).sum(-1)
        lo = mi * e_local
        mine = (top_idx >= lo) & (top_idx < lo + e_local) & (pos < cap)
        slot = jnp.where(mine, pos, cap)
        eidx = jnp.where(mine, top_idx - lo, 0)

        buf = jnp.zeros((e_local, cap + 1, d), x_l.dtype)
        for k in range(m.top_k):
            buf = buf.at[eidx[:, k], slot[:, k]].add(jnp.where(mine[:, k, None], x_l, 0))
        h = jnp.einsum("ecd,edf->ecf", buf[:, :cap], w_in)
        if gated:
            h = act_fn(cfg.act, jnp.einsum("ecd,edf->ecf", buf[:, :cap], w_gate)) * h
        else:
            h = act_fn(cfg.act, h)
        y_e = jnp.einsum("ecf,efd->ecd", h, w_out)
        y_e = jnp.pad(y_e, ((0, 0), (0, 1), (0, 0)))
        out = jnp.zeros((tl, d), x_l.dtype)
        for k in range(m.top_k):
            gk = (top_vals[:, k] * mine[:, k]).astype(x_l.dtype)
            out = out + gk[:, None] * y_e[eidx[:, k], slot[:, k]]
        # combine: bf16 reduce-scatter over the expert shards -> seq shards
        return jax.lax.psum_scatter(out, "model", scatter_dimension=0, tiled=True)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(x_spec, w_specs),
                       out_specs=out_spec, axis_names=manual, check_vma=False)
    return fn(xt, weights)


def _topk_renorm(m: MoEConfig, logits):
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, m.top_k)
    top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)
    return top_vals, top_idx, None


def _expert_ffn_grouped(cfg: ArchConfig, p, x_e):
    """x_e: (G, E, Cg, d) -> (G, E, Cg, d)."""
    h = jnp.einsum("gecd,edf->gecf", x_e, p["w_in"])
    if "w_gate" in p:
        h = act_fn(cfg.act, jnp.einsum("gecd,edf->gecf", x_e, p["w_gate"])) * h
    else:
        h = act_fn(cfg.act, h)
    return jnp.einsum("gecf,efd->gecd", h, p["w_out"])


def _dispatch_moe(cfg: ArchConfig, p, xt, top_vals, top_idx):
    """Capacity-based dispatch/combine (GShard einsum formulation)."""
    m = cfg.moe
    t, d = xt.shape
    capacity = max(int(t * m.top_k * m.capacity_factor / m.n_experts), 4)
    # position of each (token, k) within its expert queue
    onehot = jax.nn.one_hot(top_idx, m.n_experts, dtype=jnp.int32)  # (T,K,E)
    flat = onehot.reshape(t * m.top_k, m.n_experts)
    pos_in_expert = jnp.cumsum(flat, axis=0) - flat  # (T*K, E)
    pos = (pos_in_expert * flat).sum(-1).reshape(t, m.top_k)  # (T,K)
    keep = pos < capacity
    # dispatch tensor (T, K, E, C) one-hot -> combined over K below
    disp = (
        jax.nn.one_hot(top_idx, m.n_experts, dtype=xt.dtype)[..., None]
        * jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity + 1, dtype=xt.dtype)[:, :, None, :-1]
    )  # (T,K,E,C)
    comb = disp * top_vals[..., None, None].astype(xt.dtype)
    disp_te = disp.sum(1)  # (T,E,C) 0/1
    x_e = jnp.einsum("tec,td->ecd", disp_te, xt)  # all-to-all boundary
    y_e = _expert_ffn(cfg, p, x_e)
    out = jnp.einsum("tec,ecd->td", comb.sum(1), y_e)
    return out
