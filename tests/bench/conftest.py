"""Fixtures of the benchmark's tests: the benchmark's cells at a size the
CPU runs in seconds, in a manifest of their own."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# each cell shrunk to seconds on a CPU: fewer devices, iterations and
# samples; every width (dim, classes, conv channels) as configured.  The
# CPU computes float32 matmuls in full at its default precision, where the
# TPU rounds their operands to bfloat16, so the reference models that.
CPU = {"matmul_operands": "float32"}
TINY = {
    "fleet16k-ell": ({"m": 48, "n_train": 192, "n_test": 40, **CPU},
                     {"T": 6, "eval_every": 4}),
    "paper-lenet-grid": ({"n_train": 200, "n_test": 40, **CPU},
                         {"T": 5, "eval_every": 3, "seeds_per_request": 1,
                          "max_cells": 4}),
}


def make_tiny(dest: Path) -> "object":
    """A copy of the benchmark under ``dest`` with every cell at TINY size
    and the committed limits; returns its Manifest."""
    from bench.harness import BENCH_DIR, Manifest

    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    for d in ("drivers", "metrics", "limits"):
        shutil.copytree(BENCH_DIR / d, dest / d)
    for d in ("configs", "traffic"):
        (dest / d).mkdir()
    for wl in data["workloads"]:
        cfg_over, tr_over = TINY[wl["name"]]
        cfg = json.loads((BENCH_DIR / "configs" / f"{wl['config']}.json").read_text())
        tr = json.loads((BENCH_DIR / "traffic" / f"{wl['traffic']}.json").read_text())
        cfg.update(cfg_over)
        tr.update(tr_over)
        (dest / "configs" / f"{wl['config']}.json").write_text(json.dumps(cfg))
        (dest / "traffic" / f"{wl['traffic']}.json").write_text(json.dumps(tr))
    return Manifest(data, dest)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny(tmp_path_factory.mktemp("bench"))


def run_tiny(manifest, workload: str, seed: int = 2**31 + 7, seconds: float = 0.5,
             traced: bool = False) -> dict:
    """One run of a tiny cell through the harness, without the look for a
    chip."""
    import time

    import jax

    from bench.harness import run

    return run(workload, seed, seconds, traced, manifest=manifest,
               devices=jax.devices()[:1], device_kind="", t0=time.perf_counter(),
               log=lambda s: None)
