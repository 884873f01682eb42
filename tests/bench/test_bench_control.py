"""The comparison that decides ``correct`` fails the control: the plain
reference computed at bfloat16, put in the program's place."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check
from conftest import CELLS


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tiny, workload):
    wl = tiny.workload(workload)
    config, traffic = tiny.config(wl["config"]), tiny.traffic(wl["traffic"])
    cell = tiny.driver(traffic["driver"]).Cell(config, traffic,
                                               np.random.default_rng(5))
    ref, low = cell.reference(), cell.reference(dtype=jnp.bfloat16)
    limits = tiny.limits(workload)
    for seed, policy in ((11, "efhc"), (12, "global"), (13, "gossip")):
        ctl = low.answer(seed, policy, seed + 2)
        judged = check.judge(check.compare(
            ctl.out, ref.replay(ctl), tiny.compared_iterations(workload)),
            limits)
        assert not all(j["ok"] for j in judged), judged


def test_highest_precision_look_compares_the_whole_horizon(tiny):
    from bench.calibrate import readings

    out = readings("paper-lenet-grid", 7, 2, 2, tiny, log=lambda s: None,
                   precision="highest")
    assert out["precision"] == "highest" and out["control"] == []
    T = tiny.traffic("serve-grid16")["T"]
    for r in out["program"]:
        assert len(r["curve"]["loss_gap"]) == T and len(r["curve"]["acc_gap"]) == T
    assert out["program_max"]["link_mismatch"] == 0
    assert out["program_max"]["loss_gap"] < 1e-4
