"""On-chip smoke test of the EF-HC fleet engine.

Drives the main path once through the entry points users call
(``repro.api`` and ``repro.fl.simulator``) and checks the results by the
repository's own means: the jnp paths as references for the Pallas
kernels, the host CPU as the reference for the chip, the single-device
engine as the reference for the sharded one.

    python3 chip_smoke.py            # one TPU chip: every phase below
    python3 chip_smoke.py --chips 4  # four TPU chips: the sharded engine only

Phases on one chip:
  device   the first JAX device must be a TPU, else exit 1 with no result;
  paper    configs.PAPER_FMNIST_LENET (m=10, LeNet-style cnn, 28x28) for
           20 iterations through api.simulate with mix_impl="dense" and
           "pallas" (compiled kernels) on the chip, at default and at
           highest matmul precision, against the same spec on the host
           CPU; plus the kernels against jnp at that shape and a probe of
           the f32 matmul precision the consensus step needs;
  fleet    m=16384 svm at dim=784 (D=7850) on an rgg with edge_dropout,
           summary trace, through simulator.run with mix_impl="sparse" and
           "sparse_pallas", then the trigger, gather-mix and dense mix
           kernels against jnp at that shape;
  service  requests of two signatures through api.serve; every report
           must be ok, with nothing quarantined.

With --chips 4: the sharded engine (mix_impl="sharded", S=4) at
m=131072, D=7850 with each chip's peak memory, then at m=16384 against
the sparse engine on one chip.

The last line of standard output is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}},
printed only when every phase passed.  The persistent compilation cache
goes where JAX_COMPILATION_CACHE_DIR says, else to <checkout>/.jax_cache.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# --- tolerances, each with its reason ----------------------------------------
# Same chip, kernel path vs jnp path, or sharded vs single-device engine:
# both run the consensus step in full f32 (Precision.HIGHEST) and differ
# only in summation order, a few f32 ulps per step.  Trigger events,
# link counts and degrees must match exactly; floats to SAME_CHIP_RTOL
# after the horizon's compounding.  consensus_err is a sum of squared
# deviations (and hierarchical under sharding), so it gets CONSENSUS_RTOL.
SAME_CHIP_RTOL = 1e-4
CONSENSUS_RTOL = 1e-3
# One kernel call against its jnp reference on the same chip: f32
# summation order only.
KERNEL_RTOL = 1e-5
# Chip vs host CPU.  At the TPU's default precision the model's own f32
# matmuls and convolutions round their operands to bf16 (~2^-9 relative)
# while the CPU computes in f32; at iteration 0 no device has broadcast yet
# (w == w_hat), so the per-device loss differs by that rounding alone
# (XBACKEND_LOSS0_RTOL).  Later iterations are not compared at default
# precision: the rounding moves a trigger that sits at its threshold, and
# from there the runs mix differently.  The strict comparison runs the chip
# under jax.default_matmul_precision("highest") (the CPU computes f32
# either way): then only f32 summation order and transcendental rounding
# differ, compounding over 20 SGD steps, so trigger events, link counts and
# degrees must be equal and float channels agree to XBACKEND_RTOL.
# Accuracy is an argmax: a near-tied pair of logits may still flip, and
# each flip moves it by 1 / (m * n_test) = 1e-4, so it gets an absolute
# XBACKEND_ACC_ATOL of a few flips.
XBACKEND_LOSS0_RTOL = 1e-2
XBACKEND_RTOL = 1e-4
XBACKEND_ACC_ATOL = 5e-4
INT_CHANNELS = ("v", "comm_count", "deg")
FLOAT_CHANNELS = ("loss", "acc", "tx_time", "util", "bandwidths")

PAPER_ITERS = 20
FLEET_M, FLEET_DIM, FLEET_ITERS = 16384, 784, 4
BIG_M = 131072


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Collects failed checks so one run reports every failure."""

    def __init__(self):
        self.failed: list[str] = []

    def expect(self, ok: bool, what: str) -> None:
        log(f"  [{'ok' if ok else 'FAIL'}] {what}")
        if not ok:
            self.failed.append(what)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def finite(res) -> bool:
    return bool(np.isfinite(res.loss).all() and np.isfinite(res.acc).all()
                and np.isfinite(res.consensus_err).all())


def compare(ck: Checks, label: str, got, want, rtol: float = SAME_CHIP_RTOL,
            acc_atol: float | None = None) -> None:
    """Integer channels equal; float channels (every iteration, every
    device) to ``rtol``, accuracy to ``acc_atol`` absolute when given."""
    for f in INT_CHANNELS:
        a, b = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        ck.expect(np.array_equal(a, b),
                  f"{label}: {f} equal ({int((a != b).sum())} differ)")
    for f in FLOAT_CHANNELS:
        if f == "acc" and acc_atol is not None:
            d = float(np.max(np.abs(np.asarray(got.acc, np.float64)
                                    - np.asarray(want.acc, np.float64))))
            ck.expect(d <= acc_atol, f"{label}: acc abs diff {d:.3e} <= "
                      f"{acc_atol}")
            continue
        e = rel_err(getattr(got, f), getattr(want, f))
        ck.expect(e <= rtol, f"{label}: {f} rel err {e:.3e} <= {rtol}")
    e = rel_err(got.consensus_err, want.consensus_err)
    ck.expect(e <= CONSENSUS_RTOL,
              f"{label}: consensus_err rel err {e:.3e} <= {CONSENSUS_RTOL}")


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def flat_dim(spec) -> int:
    from repro.fl.modelspec import make_model_spec

    return make_model_spec(spec.model, dim=spec.dim,
                           n_classes=spec.n_classes).flat_dim


def paper_spec(mix_impl: str):
    from repro import api
    from repro.configs import PAPER_FMNIST_LENET as exp

    return api.ScenarioSpec(
        m=exp.m, topology=exp.topology, time_varying="edge_dropout",
        model=exp.model, dim=exp.dim, n_classes=exp.n_classes,
        labels_per_device=exp.labels_per_device, smooth=2, n_train=6000,
        n_test=1000, r=exp.r, b_mean=exp.b_mean, sigma_n=exp.sigma_n,
        alpha0=exp.alpha0, iters=PAPER_ITERS, eval_every=5, trace="full",
        mix_impl=mix_impl)


def phase_precision(ck: Checks) -> None:
    """Consensus P @ W at default and at full f32 precision on the chip,
    against float64 on the host: whether the explicit precision matters."""
    import jax
    import jax.numpy as jnp

    from repro.core import consensus

    rng = np.random.default_rng(0)
    m, n = 10, 7850
    a = rng.random((m, m)) < 0.4
    a = np.triu(a, 1)
    a = a | a.T
    deg = a.sum(1)
    p = np.where(a, 1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :])), 0.0)
    p[np.diag_indices(m)] = 1.0 - p.sum(1)  # Metropolis: doubly stochastic
    w = rng.normal(size=(m, n))
    want = p @ w
    pj, wj = jnp.asarray(p, jnp.float32), jnp.asarray(w, jnp.float32)
    for name, prec in (("default", jax.lax.Precision.DEFAULT),
                       ("highest", consensus.MIX_PRECISION)):
        got = jnp.matmul(pj, wj, precision=prec)
        rows = jnp.matmul(pj, jnp.ones((m, 1), jnp.float32), precision=prec)
        log(f"  P @ W at {name} precision: rel err {rel_err(got, want):.3e}, "
            f"max |row sum - 1| {float(jnp.max(jnp.abs(rows - 1.0))):.3e}")
    e = rel_err(consensus.mix_dense(pj, wj), want)
    ck.expect(e <= KERNEL_RTOL, f"mix_dense vs float64: rel err {e:.3e}")


def phase_kernels_paper(ck: Checks) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import consensus
    from repro.kernels.mixing import ops as mixing_ops
    from repro.kernels.trigger import ops as trigger_ops

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    m, n = 10, flat_dim(paper_spec("pallas"))
    w = jax.random.normal(k1, (m, n))
    wh = w + 0.01 * jax.random.normal(k2, (m, n))
    p = jax.nn.softmax(jax.random.normal(k3, (m, m)), axis=1)
    got = trigger_ops.trigger_sq(w, wh, interpret=False)
    e = rel_err(got, jnp.sum((w - wh) ** 2, axis=1))
    ck.expect(e <= KERNEL_RTOL, f"trigger_sq kernel vs jnp: rel err {e:.3e}")
    got = mixing_ops.mix(p, w, interpret=False)
    e = rel_err(got, consensus.mix_dense(p, w))
    ck.expect(e <= KERNEL_RTOL, f"mix kernel vs jnp: rel err {e:.3e}")


def phase_paper(ck: Checks) -> None:
    import jax

    from repro import api
    from repro.core.efhc import EFHCConfig

    phase_precision(ck)
    phase_kernels_paper(ck)
    ck.expect(EFHCConfig(mix_impl="pallas").pallas_interpret() is False,
              "EFHCConfig.pallas_interpret() is False on the chip")
    runs = {}
    for prec in ("default", "highest"):
        for impl in ("dense", "pallas"):
            t0 = time.perf_counter()
            with jax.default_matmul_precision(prec):
                runs[impl, prec] = api.simulate(paper_spec(impl))
            log(f"  tpu {impl} at {prec} precision: "
                f"{time.perf_counter() - t0:.1f}s incl. compile, "
                f"flat_dim={runs[impl, prec].model_dim}")
    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        # a fresh provider stages fresh arrays, so the engine is built and
        # compiled for the CPU instead of reusing the chip's
        cpu = api.simulate(paper_spec("dense"), provider=api.SyntheticProvider())
    log(f"  cpu dense: {time.perf_counter() - t0:.1f}s incl. compile")
    for name, res in (*((f"tpu {i} {p}", r) for (i, p), r in runs.items()),
                      ("cpu", cpu)):
        ck.expect(finite(res), f"{name}: losses and accuracies finite")
        log(f"  {name}: v fired {int(res.v.sum())}, comm_count "
            f"{int(res.comm_count.sum())}, final acc {float(res.acc[-1]):.4f}, "
            f"final mean loss {float(res.loss[-1].mean()):.4f}")
    for prec in ("default", "highest"):
        compare(ck, f"tpu pallas vs tpu dense at {prec} precision",
                runs["pallas", prec], runs["dense", prec])
    for impl in ("dense", "pallas"):
        res = runs[impl, "default"]
        e0 = rel_err(res.loss[0], cpu.loss[0])
        ck.expect(e0 <= XBACKEND_LOSS0_RTOL,
                  f"tpu {impl} at default precision vs cpu: iteration-0 loss "
                  f"rel err {e0:.3e} <= {XBACKEND_LOSS0_RTOL}")
        compare(ck, f"tpu {impl} at highest precision vs cpu",
                runs[impl, "highest"], cpu, rtol=XBACKEND_RTOL,
                acc_atol=XBACKEND_ACC_ATOL)


def fleet_setup(m: int, iters: int):
    from repro.core.topology import fleet_radius, make_process
    from repro.data.loader import FederatedBatches
    from repro.data.partition import by_labels
    from repro.data.synthetic import image_dataset
    from repro.fl.simulator import SimConfig, make_eval_fn

    x, y = image_dataset(4 * m, dim=FLEET_DIM, seed=0)
    xt, yt = image_dataset(400, dim=FLEET_DIM, seed=1)
    parts = by_labels(y, m, 3)
    graph = make_process(m, "rgg", radius=fleet_radius(m),
                         time_varying="edge_dropout", drop=0.3, seed=0)
    sim = SimConfig(m=m, model="svm", dim=FLEET_DIM, iters=iters,
                    trace="summary", mix_impl="sparse")
    eval_fn = make_eval_fn(sim, xt, yt)
    return (sim, graph, eval_fn,
            lambda: FederatedBatches(x, y, parts, sim.batch, seed=2))


def fleet_run(sim, graph, eval_fn, batches, label: str):
    from repro.fl.simulator import run

    t0 = time.perf_counter()
    res = run(sim, graph, batches(), eval_fn, eval_every=sim.iters)
    log(f"  {label}: {time.perf_counter() - t0:.1f}s incl. compile, "
        f"v fired {int(res.v.sum())}, final acc {float(res.acc[-1]):.4f}")
    return res


def phase_fleet(ck: Checks) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import consensus
    from repro.kernels.mixing import ops as mixing_ops
    from repro.kernels.trigger import ops as trigger_ops

    dev = jax.devices()[0]
    sim, graph, eval_fn, batches = fleet_setup(FLEET_M, FLEET_ITERS)
    nl = graph.neighbors()
    d = flat_dim(sim)
    log(f"  m={sim.m} D={d} edges={graph.edges.n_edges} d_max={nl.d_max}")

    runs = {}
    for impl in ("sparse", "sparse_pallas"):
        runs[impl] = fleet_run(dataclasses.replace(sim, mix_impl=impl), graph,
                               eval_fn, batches, impl)
        ck.expect(finite(runs[impl]), f"{impl}: losses and accuracies finite")
        log(f"  peak_bytes_in_use after {impl}: {peak_bytes(dev)}")
    compare(ck, "sparse_pallas vs sparse", runs["sparse_pallas"],
            runs["sparse"])

    # each kernel against its jnp path at the fleet's shape: many row
    # blocks, padded columns and (dense mix) a 64-step contraction; after
    # the runs, so the peak above is the engine's own
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(2), 4)
    w = jax.random.normal(k1, (sim.m, d))
    wh = w + 0.01 * jax.random.normal(k2, (sim.m, d))
    e = rel_err(trigger_ops.trigger_sq(w, wh, interpret=False),
                jnp.sum((w - wh) ** 2, axis=1))
    ck.expect(e <= KERNEL_RTOL, f"trigger_sq kernel vs jnp at m={sim.m}: "
              f"rel err {e:.3e}")
    del wh
    p_off = jnp.where(jnp.asarray(nl.mask),
                      jax.random.uniform(k3, nl.idx.shape) / nl.d_max, 0.0)
    p_diag = 1.0 - p_off.sum(1)
    idx = jnp.asarray(nl.idx)
    e = rel_err(mixing_ops.mix_sparse(idx, p_diag, p_off, w, interpret=False),
                consensus.mix_sparse(idx, p_diag, p_off, w))
    ck.expect(e <= KERNEL_RTOL, f"gather-mix kernel vs jnp at m={sim.m}: "
              f"rel err {e:.3e}")
    del p_off, p_diag
    u = jax.random.uniform(k4, (sim.m, sim.m))
    p = u / u.sum(axis=1, keepdims=True)  # row-stochastic, every entry > 0
    del u
    e = rel_err(mixing_ops.mix(p, w, interpret=False),
                consensus.mix_dense(p, w))
    ck.expect(e <= KERNEL_RTOL, f"dense mix kernel vs jnp at m={sim.m}: "
              f"rel err {e:.3e}")


def phase_service(ck: Checks) -> None:
    from repro import api

    a = api.ScenarioSpec(m=10, iters=20, eval_every=5, r=50.0, seeds=(0, 1))
    b = api.ScenarioSpec(m=64, topology="er", iters=20, eval_every=5,
                         r=50.0, mix_impl="sparse")
    reqs = [a, dataclasses.replace(a, policy="gossip", seeds=(2,)),
            b, dataclasses.replace(b, policy="zero")]
    t0 = time.perf_counter()
    reports = api.serve(reqs, max_cells=4)
    log(f"  {len(reqs)} requests, 2 signatures: "
        f"{time.perf_counter() - t0:.1f}s incl. compile")
    ck.expect(len(reports) == len(reqs), f"{len(reports)} reports")
    for rep in reports:
        ck.expect(rep.ok and not rep.quarantined,
                  f"request {rep.request_id}: ok={rep.ok} quarantined="
                  f"{rep.quarantined} error={rep.error}")
        for s, res in rep.results.items():
            ck.expect(finite(res), f"request {rep.request_id} seed {s} finite")
    if reports and reports[0].ok:
        compare(ck, "service cell vs api.simulate", reports[0].result(0),
                api.simulate(a, seed=0))


def phase_sharded(ck: Checks) -> None:
    import jax

    devs = jax.devices()
    sim, graph, eval_fn, batches = fleet_setup(BIG_M, FLEET_ITERS)
    big = fleet_run(dataclasses.replace(sim, mix_impl="sharded", shards=4),
                    graph, eval_fn, batches, f"sharded S=4 m={BIG_M}")
    ck.expect(finite(big), f"sharded m={BIG_M}: losses and accuracies finite")
    # printed, not checked: this counter leaves out the compiled program's
    # temporaries, which hold the fleet state (PERF.md "Open questions")
    log(f"  peak_bytes_in_use per chip after m={BIG_M}: "
        f"{[peak_bytes(d) for d in devs[:4]]}")
    del big, graph, eval_fn, batches

    sim, graph, eval_fn, batches = fleet_setup(FLEET_M, FLEET_ITERS)
    sh = fleet_run(dataclasses.replace(sim, mix_impl="sharded", shards=4),
                   graph, eval_fn, batches, f"sharded S=4 m={FLEET_M}")
    ref = fleet_run(sim, graph, eval_fn, batches, f"sparse 1 chip m={FLEET_M}")
    compare(ck, "sharded S=4 vs sparse", sh, ref)
    log(f"  peak_bytes_in_use per chip: {[peak_bytes(d) for d in devs[:4]]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-engine phase on four chips")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro package next to {__file__}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if dev.platform != "tpu":
        print(f"chip_smoke: the first JAX device is {dev.platform!r}, not a "
              "TPU", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1

    phases = ([("sharded", phase_sharded)] if args.chips == 4 else
              [("paper", phase_paper), ("fleet", phase_fleet),
               ("service", phase_service)])
    ck = Checks()
    for name, fn in phases:
        log(f"== phase {name}")
        t0 = time.perf_counter()
        n_failed = len(ck.failed)
        try:
            fn(ck)
        except Exception as e:  # noqa: BLE001 -- report, then fail the run
            import traceback

            traceback.print_exc()
            ck.failed.append(f"{name}: {type(e).__name__}: {e}")
        status = "passed" if len(ck.failed) == n_failed else "FAILED"
        log(f"== phase {name} {status} in {time.perf_counter() - t0:.1f}s, "
            f"peak_bytes_in_use {peak_bytes(dev)}")
    if ck.failed:
        log(f"{len(ck.failed)} check(s) failed:")
        for f in ck.failed:
            log(f"  {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
