"""Runs one cell of the benchmark on the accelerator it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up, a measured window of ``--seconds``, then the check of the
window's answers against the plain reference.  The last line of standard
output is one JSON object: correct, attempted, failed, metrics, device
(and, traced, breakdown), and last the numbers compared with their
limits, which are also the last lines of standard error.  Exits non-zero
with no result where the first JAX device is not a TPU, where JAX sees
fewer chips than the cell asks for, or where the program under test is
not in the checkout.  JAX's persistent compilation cache goes where
JAX_COMPILATION_CACHE_DIR says, else to <checkout>/.jax_cache
(``harness.use_cache``).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        err(f"bench: the program under test (src/repro) is not in {ROOT}")
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from bench.harness import Manifest, run, use_cache

    use_cache(ROOT)

    manifest = Manifest.load(ROOT / "BENCHMARK.json")
    chips = manifest.workload(args.workload)["chips"]
    devs = jax.devices()
    if devs[0].platform != "tpu":
        err(f"bench: the first JAX device is {devs[0].platform!r}, not a TPU")
        return 1
    if len(devs) < chips:
        err(f"bench: {args.workload} needs {chips} chips, JAX sees {len(devs)}")
        return 1
    used = devs[:chips]
    res = run(args.workload, args.seed, args.seconds, bool(args.trace),
              manifest=manifest, devices=used, device_kind=devs[0].device_kind,
              t0=T0, log=err)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": max(res.pop("peak_bytes"))}
    if args.trace:
        device["busy_s"] = res.pop("busy_s")
        device["window_s"] = res.pop("window_s")
    checks = res.pop("checks")
    out = {"correct": res.pop("correct"), "attempted": res.pop("attempted"),
           "failed": res.pop("failed"), "metrics": res.pop("metrics"),
           "device": device, **res, "checks": checks}
    for c in checks:
        err(f"check {c['name']}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
