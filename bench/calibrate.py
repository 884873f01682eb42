"""Readings that the limits of the correctness check are set from.

    python3 bench/calibrate.py --workload <name> --seed <n> --answers 12 \
        --control 3 [--precision highest] [--out <file.json>]

In one process, at the cell's own size and load: set-up as a run makes
it, then closed-loop calls until ``--answers`` answers are sampled (as
many per call as a run checks), each compared with the cell's reference
(``Cell.reference``); then ``--control`` of those answers recomputed by the
control, the reference itself at bfloat16, in the program's place and
compared the same way.
Prints one JSON object: every number's readings for the program and the
control, and per answer each number's curve over the horizon.

``--precision highest`` is the look at what rounding does: the program
runs under ``jax.default_matmul_precision("highest")`` against the
reference with full-precision operands, every number over the whole
horizon, and no control.  Needs a TPU, like a run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _curve(got: dict, ref: dict) -> dict:
    """Per iteration: the loss gap and the largest charged margin, for
    the look at how the gaps grow over the horizon."""
    import numpy as np

    from bench import check

    cons = np.abs(np.asarray(got["consensus_err"], np.float64) - ref["consensus_err"])
    return {"loss_gap": [float(g) for g in check.loss_gaps(got, ref)],
            "trigger_margin": [float(c) for c in ref["charged"].max(axis=1)],
            "consensus_gap": [float(c) for c in cons / np.abs(ref["consensus_err"])],
            "acc_gap": [float(a) for a in np.abs(np.asarray(got["acc"], np.float64)
                                                 - ref["acc"])]}


def _short(reading: dict) -> dict:
    return {k: v for k, v in reading.items() if k != "curve"}


def readings(workload: str, seed: int, n_answers: int, n_control: int,
             manifest, log=print, precision: str | None = None) -> dict:
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import check

    wl = manifest.workload(workload)
    config, traffic = manifest.config(wl["config"]), manifest.traffic(wl["traffic"])
    iters = manifest.compared_iterations(workload)
    if precision is not None:
        iters, n_control = None, 0
    rng = np.random.default_rng(seed)
    sample = []
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        cell = manifest.driver(traffic["driver"]).Cell(config, traffic, rng)
        while len(sample) < n_answers:
            t0 = time.perf_counter()
            got = cell.call()["answers"]
            pick = rng.choice(len(got), size=min(traffic["check_answers"], len(got)),
                              replace=False)
            sample += [got[i] for i in sorted(pick)]
            log(f"call {time.perf_counter() - t0:.3f}s, {len(sample)} answers")
    sample = sample[:n_answers]
    ref = cell.reference(precision=precision)
    low = cell.reference(dtype=jnp.bfloat16) if n_control else None
    del cell
    out = {"workload": workload, "seed": seed, "precision": precision,
           "program": [], "control": []}
    for i, a in enumerate(sample):
        r = ref.replay(a)
        out["program"].append({"seed": a.seed, "policy": a.policy,
                               **check.compare(a.out, r, iters),
                               "curve": _curve(a.out, r)})
        log(f"program {_short(out['program'][-1])}")
        if i < n_control:
            ctl = low.answer(a.seed, a.policy, a.sample_seed)
            r = ref.replay(ctl)
            out["control"].append({"seed": a.seed, "policy": a.policy,
                                   **check.compare(ctl.out, r, iters),
                                   "curve": _curve(ctl.out, r)})
            log(f"control {_short(out['control'][-1])}")
    for side in ("program", "control"):
        out[f"{side}_max"] = check.worst(out[side]) if out[side] else {}
        out[f"{side}_min"] = ({k: float(np.min([r[k] for r in out[side]]))
                               for k in check.NAMES} if out[side] else {})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--answers", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--precision", choices=("highest",))
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from bench.harness import Manifest, use_cache

    use_cache(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: the first JAX device is not a TPU", file=sys.stderr)
        return 1

    res = readings(args.workload, args.seed, args.answers, args.control,
                   Manifest.load(ROOT / "BENCHMARK.json"),
                   log=lambda s: print(s, file=sys.stderr, flush=True),
                   precision=args.precision)
    text = json.dumps(res)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
