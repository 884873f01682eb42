"""Large-fleet EF-HC: hundreds-to-thousands of devices on one host.

The paper's regime is a *massive* fleet of resource-constrained edge
devices on a sparse D2D graph.  Two things made m > ~64 infeasible before
this scenario existed: the scan ys carried dense (m, m) bool link matrices
every iteration (O(T m^2) trajectory memory), and the mixing/trigger
kernels were dead code.  This example turns both knobs:

* ``--trace packed``  bit-packs the link matrices inside the scan
  (8x smaller, losslessly unpacked on access) -- good to m ~ 512;
* ``--trace summary`` keeps only per-device link counts and degrees
  (O(T m)) -- the m = 1024+ mode;
* ``--mix-impl pallas`` routes aggregation + trigger deviation through the
  fused kernels (interpret mode off-TPU, compiled on TPU);
* ``--mix-impl sparse`` (or ``sparse_pallas``) aggregates over the padded
  neighbor list instead of the dense (m, m) matrix -- O(m d n) per Event-3
  instead of O(m^2 n), which is what opens m = 2048/4096 fleets
  (DESIGN.md "Sparse mixing"); and
* ``--shards 8`` partitions the fleet across 8 devices with the sharded
  fleet engine (shard_map + halo exchange, DESIGN.md "Sharded fleet
  engine") -- the m >= 100k mode.  Off-accelerator the devices are forced
  host devices, so the flag must be handled before jax initializes (which
  is why every jax import in this script lives inside ``main``).

    PYTHONPATH=src python examples/large_fleet.py [--m 4096] [--iters 60]
        [--trace summary] [--mix-impl sparse]
    PYTHONPATH=src python examples/large_fleet.py --m 4096 --shards 8 \
        --parity-check   # sharded vs single-device, bit-exact
"""
import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=512)
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--trace", default="summary",
                    choices=("full", "packed", "summary"))
    ap.add_argument("--mix-impl", default="dense",
                    help="dense|delta|pallas|sparse|sparse_delta|"
                         "sparse_pallas|sharded (validated after jax import)")
    ap.add_argument("--dim", type=int, default=64,
                    help="input dimension (small keeps the demo CPU-friendly)")
    ap.add_argument("--shards", type=int, default=1,
                    help="partition the fleet across this many devices with "
                         "the sharded engine (implies --mix-impl sharded)")
    ap.add_argument("--parity-check", action="store_true",
                    help="after a sharded run, rerun on a single device with "
                         "mix_impl=sparse and assert the trajectories match")
    args = ap.parse_args()

    if args.shards > 1 or args.mix_impl == "sharded":
        args.mix_impl = "sharded"
        args.shards = max(args.shards, 2)
        # forced host devices must exist before jax initializes
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.shards} "
            + os.environ.get("XLA_FLAGS", ""))
    if args.parity_check and args.mix_impl != "sharded":
        ap.error("--parity-check compares a sharded run; pass --shards")

    import dataclasses

    import numpy as np

    from repro.compile_cache import use_compile_cache
    from repro.core.efhc import MIX_IMPLS
    from repro.core.topology import fleet_radius, make_process
    from repro.data.loader import FederatedBatches
    from repro.data.partition import by_labels
    from repro.data.synthetic import image_dataset
    from repro.fl import trace as trace_mod
    from repro.fl.simulator import SimConfig, make_eval_fn, run
    from repro.fl.trace import link_bytes_per_iter

    if args.mix_impl not in (*MIX_IMPLS, "sharded"):
        ap.error(f"unknown --mix-impl {args.mix_impl!r}")
    use_compile_cache()
    if args.mix_impl == "sharded" and args.trace != "summary":
        ap.error("the sharded engine keeps only summary traces")

    m = args.m
    # scale the pool with the fleet so the 3-labels-per-device partition
    # leaves no device empty at m >= 2048
    x, y = image_dataset(max(4000, 4 * m), seed=0, dim=args.dim)
    xt, yt = image_dataset(800, seed=1, dim=args.dim)
    parts = by_labels(y, m, 3)
    graph = make_process(m, "rgg", radius=fleet_radius(m),
                         time_varying="edge_dropout", drop=0.3, seed=0)
    sim = SimConfig(m=m, iters=args.iters, dim=args.dim, r=50.0,
                    trace=args.trace, mix_impl=args.mix_impl,
                    shards=args.shards)
    eval_fn = make_eval_fn(sim, xt, yt)
    mk_batches = lambda: FederatedBatches(x, y, parts, sim.batch, seed=2)

    per_iter = link_bytes_per_iter(m, args.trace)
    full_iter = link_bytes_per_iter(m, "full")
    nl = graph.neighbors()  # edge-native: no dense (m, m) staging view
    shard_note = f" x {args.shards} shards" if args.shards > 1 else ""
    print(f"fleet: m={m}, T={args.iters}, trace={args.trace}, "
          f"mix_impl={args.mix_impl}{shard_note}, "
          f"base edges={graph.edges.n_edges}, d_max={nl.d_max}")
    print(f"link-trace memory: {per_iter * args.iters / 1e6:.1f} MB "
          f"(dense would be {full_iter * args.iters / 1e6:.1f} MB)")

    t0 = time.time()
    res = run(sim, graph, mk_batches(), eval_fn, eval_every=20)
    wall = time.time() - t0

    deg = res.deg.mean()
    print(f"\n{args.iters} iters in {wall:.1f}s "
          f"({args.iters / wall:.1f} iters/s incl. compile)")
    print(f"final mean accuracy     {res.acc[-1]:.3f}")
    print(f"trigger rate            {res.v.mean():.3f}")
    print(f"mean physical degree    {deg:.1f}")
    print(f"links used / available  {(res.comm_count.sum() / max(res.deg.sum(), 1)):.3f}")
    print(f"mean tx time / iter     {res.tx_time.mean():.4f}")
    print(f"mean utilization        {res.util.mean():.4f}")
    print(f"consensus error         {res.consensus_err[0]:.3g} -> "
          f"{res.consensus_err[-1]:.3g}")
    if args.trace != "summary":
        # counts straight off the stored words: packed traces are popcounted,
        # never unpacked (fl/trace.stored_link_counts)
        counts = trace_mod.stored_link_counts(res._comm, res.trace, "comm")
        linked = (counts > 0).all(-1)  # (T,): every device on >= 1 link
        note = (f"first all-devices-linked round {int(np.argmax(linked)) + 1}"
                if linked.any() else "no round linked every device")
        print(f"info-flow trace kept: comm stored {res._comm.shape} ({note})")

    if args.parity_check:
        print(f"\nparity check: rerunning m={m} on a single device "
              f"(mix_impl=sparse) ...")
        ref = run(dataclasses.replace(sim, mix_impl="sparse", shards=1),
                  graph, mk_batches(), eval_fn, eval_every=20)
        for f in ("v", "comm_count", "deg", "loss", "tx_time", "util",
                  "acc", "bandwidths"):
            got, want = np.asarray(getattr(res, f)), np.asarray(getattr(ref, f))
            assert (got == want).all(), f"sharded != single-device on {f}"
        # hierarchical psum reduction: fp32-tolerance, not bit-exact
        np.testing.assert_allclose(res.consensus_err, ref.consensus_err,
                                   rtol=1e-5)
        print(f"parity OK: {args.shards}-shard trajectories match the "
              f"single-device run bit-for-bit")


if __name__ == "__main__":
    main()
