"""What the EF-HC drivers share: the cell's data and fabric, made from the
configuration, the adapter from the configuration's words to the
program's knobs, the answers a window produced, and the plain reference
that replays them."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
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


class Replay:
    """The plain reference of one scenario (``bench.reference.efhc``),
    staging each answer's minibatches from its ``sample_seed``.
    ``precision="highest"`` computes the models' matmuls with full-precision
    operands, as the program does under that default matmul precision."""

    def __init__(self, sc: ref_efhc.Scenario, data: Data, dtype=jnp.float32,
                 precision: str | None = None):
        if precision == "highest":
            sc = dataclasses.replace(sc, matmul_operands="float32")
        elif precision is not None:
            raise ValueError(f"precision {precision!r}: only 'highest'")
        self.sc, self.parts = sc, data.parts
        self.ref = ref_efhc.Reference(sc, data.x, data.y, data.x_test,
                                      data.y_test, dtype=dtype)

    def _idx(self, sample_seed: int) -> np.ndarray:
        return gen.stage(self.parts, self.sc.batch, sample_seed, self.sc.T)

    def replay(self, answer: Answer) -> dict:
        """The reference's trajectory following the answer's broadcast
        decisions: the channels ``check.compare`` reads, ``charged``
        included."""
        return self.ref.run(answer.seed, answer.policy,
                            self._idx(answer.sample_seed),
                            forced_v=answer.out["v"])

    def answer(self, seed: int, policy: str, sample_seed: int) -> Answer:
        """The reference in the program's place, on its own decisions."""
        return Answer(seed, policy, sample_seed,
                      self.ref.run(seed, policy, self._idx(sample_seed)))
