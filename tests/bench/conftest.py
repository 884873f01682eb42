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

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [wl["name"] for wl in BENCHMARK["workloads"]]
# tiny/<workload>.json: each cell's size on a CPU, as overrides of its
# configuration and traffic mix
TINY_DIR = Path(__file__).resolve().parent / "tiny"


def make_tiny(dest: Path, data: dict = BENCHMARK, src: Path | None = None,
              tiny: Path = TINY_DIR) -> "object":
    """A copy under ``dest`` of the benchmark ``data`` describes, its files
    from ``src`` (the committed benchmark's by default), with every cell at
    the size ``tiny`` gives it and the committed limits; returns its
    Manifest."""
    from bench.harness import BENCH_DIR, Manifest

    src = BENCH_DIR if src is None else src
    for d in src.iterdir():
        if d.is_dir() and d.name not in ("configs", "traffic", "__pycache__"):
            shutil.copytree(d, dest / d.name,
                            ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("configs", "traffic"):
        (dest / d).mkdir()
    for wl in data["workloads"]:
        size = json.loads((tiny / f"{wl['name']}.json").read_text())
        cfg = json.loads((src / "configs" / f"{wl['config']}.json").read_text())
        tr = json.loads((src / "traffic" / f"{wl['traffic']}.json").read_text())
        cfg.update(size["config"])
        tr.update(size["traffic"])
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
