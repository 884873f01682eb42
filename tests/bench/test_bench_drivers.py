"""Each driver at a tiny size on the CPU, called directly, and each cell
driven through a whole run of the harness (all but the look for a chip):
the window's answers check out against the plain reference."""
from __future__ import annotations

import numpy as np
import pytest

from conftest import CELLS, run_tiny


@pytest.mark.parametrize("workload", CELLS)
def test_driver_call(tiny, workload):
    wl = tiny.workload(workload)
    config, traffic = tiny.config(wl["config"]), tiny.traffic(wl["traffic"])
    cell = tiny.driver(traffic["driver"]).Cell(config, traffic,
                                               np.random.default_rng(3))
    rec = cell.call()
    T, m = traffic["T"], config["m"]
    n = len(traffic.get("policies", [traffic.get("policy")])) * traffic.get(
        "seeds_per_request", 1)
    assert len(rec["answers"]) == n
    assert rec["dev_iters"] == m * T * n
    for a in rec["answers"]:
        assert a.out["loss"].shape == (T, m) and np.isfinite(a.out["loss"]).all()
        assert a.out["deg"].shape == (T, m)
    work = cell.iteration_work([rec])
    assert work["flops"] > 0 and work["bytes"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_run_is_correct(tiny, workload):
    res = run_tiny(tiny, workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"device_iters_per_s", "setup_s"}
    assert res["metrics"]["device_iters_per_s"]["value"] > 0
    assert [c["name"] for c in res["checks"]][-1] == "tx_gap"


def test_traced_run_reports_per_layer_metrics(tiny):
    res = run_tiny(tiny, "paper-lenet-grid", traced=True)
    assert res["correct"]
    # the CPU has no device plane: what the trace gives is there, the
    # step's share of a chip's peak is not (no peaks for a CPU)
    assert {"device_idle_share", "service_stage_share"} <= set(res["metrics"])
    assert "step_mfu" not in res["metrics"]
    assert res["breakdown"]["idle_gaps"]
