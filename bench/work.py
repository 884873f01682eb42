"""Operations and bytes of one EF-HC iteration, from the cell's shapes.

The counts are the algorithm's, whatever implements it: Events 1-4 plus
the evaluation the scenario schedules, each array read once and written
once, the mix charged for the links actually used.  The dense, ELL and
Pallas paths of one cell therefore get the same count.

Per iteration of one simulated cell (m devices, D parameters each, f32):

* Event 2   ||w - w_hat||: 3 m D operations.
* Event 3   the mix over the realized links: 2 D (m + links) operations,
            links being the directed links used in the iteration.
* Event 4   the minibatch's forward and backward passes, m * batch samples
            (the first layer needs no input gradient: 3 fwd - fwd_first),
            and the SGD update, 2 m D.
* metrics   the consensus error over the updated fleet, 3 m D.
* eval      m * n_test forward passes at each scheduled evaluation
            (iterations 0, E, 2E, ... and the last), spread over T.

Bytes: w and w_hat read and written once (4 m D words), the minibatch rows
read once, and at each evaluation w and the test rows read once more.
"""
from __future__ import annotations

import math

F32 = 4


def _conv_out(side: int) -> int:
    return -(-side // 2)


def model_shapes(model: str, dim: int, n_classes: int,
                 cnn: tuple[int, int, int] = (8, 16, 32)) -> dict:
    """Parameters per device, forward operations per sample, and the
    forward operations of the first layer per sample."""
    C = n_classes
    if model == "svm":
        fwd = 2 * dim * C
        return {"D": dim * C + C, "fwd": fwd, "fwd_first": fwd}
    if model == "cnn":
        c1, c2, hid = cnn
        side = math.isqrt(dim)
        s1 = _conv_out(side)
        s2 = _conv_out(s1)
        feat = s2 * s2 * c2
        conv1 = 2 * side * side * 9 * c1
        conv2 = 2 * s1 * s1 * 9 * c1 * c2
        fwd = conv1 + conv2 + 2 * feat * hid + 2 * hid * C
        D = 9 * c1 + c1 + 9 * c1 * c2 + c2 + feat * hid + hid + hid * C + C
        return {"D": D, "fwd": fwd, "fwd_first": conv1}
    raise ValueError(f"no operation count for model {model!r}")


def evals_per_run(T: int, eval_every: int) -> int:
    return -(-T // eval_every) + 1


def iteration_work(config: dict, traffic: dict, links: float) -> dict:
    """{"flops", "bytes"} of one iteration of one cell of ``config`` under
    ``traffic``, ``links`` directed links used.  Nothing in it depends on
    the configuration's mixing implementation."""
    m, batch, dim = config["m"], config["batch"], config["dim"]
    n_test = config["n_test"]
    s = model_shapes(config["model"], dim, config["n_classes"],
                     tuple(config.get("cnn", (8, 16, 32))))
    D = s["D"]
    evals = evals_per_run(traffic["T"], traffic["eval_every"]) / traffic["T"]
    flops = (3 * m * D                                   # Event 2
             + 2 * D * (m + links)                       # Event 3
             + m * batch * (3 * s["fwd"] - s["fwd_first"])  # Event 4 grads
             + 2 * m * D                                 # Event 4 update
             + 3 * m * D                                 # consensus error
             + evals * m * n_test * s["fwd"])            # eval
    words = (4 * m * D + m * batch * (dim + 1)
             + evals * (m * D + n_test * (dim + 1)))
    return {"flops": float(flops), "bytes": float(words * F32)}
