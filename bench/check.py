"""The comparison that decides ``correct``.

One answer is one simulated cell's trajectory: per iteration and device
the minibatch loss, broadcast decision, links used and degree, and per
iteration the accuracy, transmission time, utilization and consensus
error.  The reference replays the answer's broadcast decisions
(``bench.reference.efhc``) and the numbers below compare the two.  Each
has a limit of its own, kept per workload in ``limits/<workload>.json``
with the readings it was set from (PERF.md).

* deg_mismatch    iterations x devices whose degree differs (Event 1's
                  realized fabric); exact, limit 0.
* link_mismatch   iterations x devices whose count of links used differs
                  (Events 1 and 3 given the decisions); exact, limit 0.
* trigger_margin  the largest distance, (|dev - thr| / (dev + thr)), of a
                  broadcast decision that disagrees with the reference's
                  own (Event 2); 0 when all agree.
* loss_gap        the largest over iterations of ||loss - loss_ref|| /
                  ||loss_ref|| over the devices (Event 4 after Event 3).
* consensus_gap   the largest relative gap of the consensus error.
* acc_gap         the largest absolute gap of the mean test accuracy.
* tx_gap          the largest relative gap of transmission time,
                  utilization and bandwidths.
"""
from __future__ import annotations

import numpy as np

NAMES = ("deg_mismatch", "link_mismatch", "trigger_margin", "loss_gap",
         "consensus_gap", "acc_gap", "tx_gap")


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def loss_gaps(got: dict, ref: dict) -> np.ndarray:
    """Per iteration, ||loss - loss_ref|| / ||loss_ref|| over the devices."""
    loss, loss_ref = (np.asarray(got["loss"], np.float64),
                      np.asarray(ref["loss"], np.float64))
    num = np.linalg.norm(loss - loss_ref, axis=1)
    return num / np.maximum(np.linalg.norm(loss_ref, axis=1), 1e-30)


def compare(got: dict, ref: dict,
            iterations: int | dict | None = None) -> dict[str, float]:
    """Numbers of one answer; NaN where the answer is not finite.  The
    numbers that follow the models' weights (trigger_margin, loss_gap,
    consensus_gap, acc_gap) cover the leading ``iterations`` iterations: one
    count for all, a count per number, or the whole horizon (None, or a
    number left out); the fabric's and the links' cover the whole horizon."""

    def k(name):
        n = iterations.get(name) if isinstance(iterations, dict) else iterations
        return slice(0, n)

    return {
        "deg_mismatch": float(np.sum(np.asarray(got["deg"]) != ref["deg"])),
        "link_mismatch": float(np.sum(np.asarray(got["comm_count"])
                                      != ref["comm_count"])),
        "trigger_margin": float(np.max(ref["charged"][k("trigger_margin")])),
        "loss_gap": float(np.max(loss_gaps(got, ref)[k("loss_gap")])),
        "consensus_gap": _rel(np.asarray(got["consensus_err"])[k("consensus_gap")],
                              ref["consensus_err"][k("consensus_gap")]),
        "acc_gap": float(np.max(np.abs(np.asarray(got["acc"], np.float64)
                                       - ref["acc"])[k("acc_gap")])),
        "tx_gap": max(_rel(got["tx_time"], ref["tx_time"]),
                      _rel(got["util"], ref["util"]),
                      _rel(got["bandwidths"], ref["bandwidths"])),
    }


def worst(readings: list[dict[str, float]]) -> dict[str, float]:
    """The largest reading of each number over answers; NaN wins."""
    out = {}
    for k in NAMES:
        vals = np.asarray([r[k] for r in readings], np.float64)
        out[k] = float("nan") if np.isnan(vals).any() else float(vals.max())
    return out


def judge(values: dict[str, float], limits: dict[str, float]) -> list[dict]:
    """[{name, value, limit, ok}] for every number the cell's limits name;
    a NaN fails."""
    return [{"name": k, "value": values[k], "limit": float(limits[k]),
             "ok": bool(values[k] <= limits[k])} for k in NAMES if k in limits]
