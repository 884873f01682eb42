"""Adding a configuration, a traffic mix or a metric takes new files and
manifest entries only: the harness finds each by its name."""
from __future__ import annotations

import json
import re
from pathlib import Path

from bench.harness import BENCH_DIR, Context, Manifest

ROOT = BENCH_DIR.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_new_files_are_found_by_name(tmp_path):
    for d in ("configs", "traffic", "metrics", "limits", "drivers"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "new-cfg.json").write_text(json.dumps({"m": 3}))
    (tmp_path / "traffic" / "new-mix.json").write_text(
        json.dumps({"driver": "new_entry", "T": 2}))
    (tmp_path / "limits" / "new-cell.json").write_text(
        json.dumps({"limits": {"loss_gap": 0.5}}))
    (tmp_path / "drivers" / "new_entry.py").write_text("class Cell:\n    kind = 'new'\n")
    (tmp_path / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return ctx.window_s * 2\n")
    data = {"workloads": [{"name": "new-cell", "config": "new-cfg",
                           "traffic": "new-mix", "chips": 1, "why": "x"}],
            "end_to_end": [{"name": "setup_s", "moves": None}],
            "per_layer": [{"name": "new_metric", "moves": "setup_s"}]}
    man = Manifest(data, tmp_path)
    wl = man.workload("new-cell")
    assert man.config(wl["config"]) == {"m": 3}
    assert man.driver(man.traffic(wl["traffic"])["driver"]).Cell.kind == "new"
    assert man.limits("new-cell") == {"loss_gap": 0.5}
    ctx = Context(setup_s=1.0, window_s=3.0, calls=[], peak_bytes=None,
                  trace=None, work={}, peaks={})
    assert man.reader("new_metric")(ctx) == 6.0
    # a per-layer metric without a workloads key goes to every cell that
    # reports the end-to-end metric it moves
    assert [m["name"] for m in man.metrics("new-cell", traced=True)] == ["new_metric"]


def test_committed_manifest_resolves():
    man = Manifest.load(ROOT / "BENCHMARK.json")
    cfg_files = {c["name"]: c["file"] for c in man.data["configs"]}
    for wl in man.data["workloads"]:
        assert NAME.match(wl["name"]) and len(wl["why"]) <= 200
        assert cfg_files[wl["config"]] == f"bench/configs/{wl['config']}.json"
        traffic = man.traffic(wl["traffic"])
        assert man.config(wl["config"])["name"] == wl["config"]
        assert hasattr(man.driver(traffic["driver"]), "Cell")
        assert set(man.limits(wl["name"])) >= {"loss_gap", "deg_mismatch"}
        names = {m["name"] for m in man.metrics(wl["name"], traced=False)}
        assert "setup_s" in names and len(names) >= 2
        assert man.metrics(wl["name"], traced=True)
    for m in man.data["end_to_end"] + man.data["per_layer"]:
        assert NAME.match(m["name"]) and callable(man.reader(m["name"]))
    assert all(Path(ROOT / p).is_dir() for p in man.data["paths"])
