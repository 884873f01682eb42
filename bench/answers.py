"""What the drivers share: the cell's data and fabric, made from the
configuration, the adapter from the configuration's words to the
program's knobs, and the answers a window produced."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import gen
from bench.reference import efhc as ref_efhc

# The configuration names the deployment's mixing; the program names its
# implementation.  This adapter is the one place that knows the program's
# knob (SimConfig.mix_impl).
MIX_IMPL = {"ell": "sparse", "dense": "dense"}

CHANNELS = ("loss", "acc", "tx_time", "util", "v", "comm_count", "deg",
            "consensus_err", "bandwidths")


def mix_impl(config: dict) -> str:
    try:
        return MIX_IMPL[config["mixing"]]
    except KeyError:
        raise ValueError(f"no program path for mixing {config['mixing']!r}; "
                         f"known: {sorted(MIX_IMPL)}") from None


@dataclasses.dataclass
class Answer:
    """One simulated cell the window produced, and how to replay it."""

    seed: int  # the run seed the program was given
    policy: str
    sample_seed: int  # the minibatch sampler's seed
    out: dict  # the program's trajectories, CHANNELS


def extract(res) -> dict:
    """Host copies of a SimResult's channels."""
    return {k: np.asarray(getattr(res, k)) for k in CHANNELS}


@dataclasses.dataclass
class Data:
    x: np.ndarray
    y: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray
    parts: list


def make_data(config: dict) -> Data:
    kw = dict(n_classes=config["n_classes"], dim=config["dim"],
              noise=config["noise"], proto_seed=config["proto_seed"],
              smooth=config["smooth"])
    x, y = gen.image_dataset(config["n_train"], seed=config["data_seed"], **kw)
    x_test, y_test = gen.image_dataset(config["n_test"],
                                       seed=config["data_seed"] + 1, **kw)
    parts = gen.by_labels(y, config["m"], config["labels_per_device"],
                          seed=config["data_seed"])
    return Data(x, y, x_test, y_test, parts)


def fabric(config: dict) -> tuple[np.ndarray, np.ndarray]:
    radius = config["radius"]
    if radius == "fleet":
        radius = gen.fleet_radius(config["m"])
    return gen.rgg_edges(config["m"], radius, config["fabric_seed"])


def scenario(config: dict, traffic: dict, edges) -> ref_efhc.Scenario:
    nbr, mask = gen.neighbours(*edges, config["m"])
    return ref_efhc.Scenario(
        model=config["model"], dim=config["dim"], n_classes=config["n_classes"],
        m=config["m"], batch=config["batch"], T=traffic["T"],
        eval_every=traffic["eval_every"], r=config["r"],
        b_mean=config["b_mean"], sigma_n=config["sigma_n"],
        alpha0=config["alpha0"], drop=config["edge_dropout"],
        process_seed=config["dropout_seed"], nbr=nbr, mask=mask,
        cnn=tuple(config.get("cnn", (8, 16, 32))),
        matmul_operands=config["matmul_operands"])


def draw_seed(rng: np.random.Generator) -> int:
    """A program seed: a whole number the program holds in int32."""
    return int(rng.integers(0, 2**30))
