"""A cell added as new files alone: a configuration, a traffic mix, a
driver, a reference module of its own, limits and a CPU size, written
beside a copy of the committed benchmark without touching one of its
files, runs through the harness and is judged by its own reference."""
from __future__ import annotations

import json
import shutil

import pytest

from bench.harness import BENCH_DIR
from conftest import BENCHMARK, make_tiny, run_tiny

DRIVER = '''"""The new cell's driver: a closed loop of repro.fl.simulator.run over
an svm fleet, judged by the cell's own reference module."""
import dataclasses
import importlib.util
from pathlib import Path

import jax.numpy as jnp

from bench import answers, scopes, work


def _reference_module():
    path = Path(__file__).resolve().parents[1] / "reference" / "svm_new.py"
    spec = importlib.util.spec_from_file_location("reference_svm_new", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    def __init__(self, config, traffic, rng):
        from repro.core.topology import EdgeList, GraphProcess
        from repro.fl.simulator import SimConfig, make_eval_fn

        self.config, self.traffic, self.rng = config, traffic, rng
        self.data = answers.make_data(config)
        self.edges = answers.fabric(config)
        self.graph = GraphProcess(
            edges=EdgeList(u=self.edges[0], v=self.edges[1], m=config["m"]),
            kind="edge_dropout", drop=config["edge_dropout"],
            seed=config["dropout_seed"])
        self.sim = SimConfig(
            m=config["m"], model=config["model"], n_classes=config["n_classes"],
            dim=config["dim"], batch=config["batch"], iters=traffic["T"],
            policy=traffic["policy"], r=config["r"], b_mean=config["b_mean"],
            sigma_n=config["sigma_n"], alpha0=config["alpha0"],
            mix_impl=answers.mix_impl(config), trace="summary")
        self.eval_fn = make_eval_fn(self.sim, self.data.x_test, self.data.y_test)

    def call(self):
        from repro.data.loader import FederatedBatches
        from repro.fl import simulator

        seed, sample_seed = answers.draw_seed(self.rng), answers.draw_seed(self.rng)
        d = self.data
        res = simulator.run(
            dataclasses.replace(self.sim, seed=seed), self.graph,
            FederatedBatches(d.x, d.y, d.parts, self.sim.batch, seed=sample_seed),
            self.eval_fn, eval_every=self.traffic["eval_every"])
        T = self.traffic["T"]
        return {"answers": [answers.Answer(seed, self.sim.policy, sample_seed,
                                           answers.extract(res))],
                "dev_iters": self.config["m"] * T, "scan_iters": T}

    def counters(self):
        return {}

    def iteration_work(self, calls):
        return work.iteration_work(self.config, self.traffic, 0.0)

    def op_scopes(self):
        return scopes.live_op_scopes("engine")

    def reference(self, dtype=jnp.float32, precision=None):
        return _reference_module().Reference(self.config, self.traffic,
                                             self.data, self.edges, dtype)
'''

REFERENCE = '''"""The new cell's plain reference: bench.reference.efhc, replaying an
answer on the minibatches its sample seed draws."""
import dataclasses

import jax.numpy as jnp

from bench import answers, gen
from bench.reference import efhc

ALPHA_SCALE = {alpha_scale}


class Reference:
    def __init__(self, config, traffic, data, edges, dtype=jnp.float32):
        sc = answers.scenario(config, traffic, edges)
        self.sc = dataclasses.replace(sc, alpha0=sc.alpha0 * ALPHA_SCALE)
        self.parts = data.parts
        self.ref = efhc.Reference(self.sc, data.x, data.y, data.x_test,
                                  data.y_test, dtype=dtype)

    def replay(self, answer):
        idx = gen.stage(self.parts, self.sc.batch, answer.sample_seed, self.sc.T)
        return self.ref.run(answer.seed, answer.policy, idx,
                            forced_v=answer.out["v"])
'''

CELL = {"name": "svm-new-cell", "config": "svm-new", "traffic": "sim-new",
        "chips": 1, "why": "a cell that brings every file of its own"}


def _add_cell(tmp_path, alpha_scale: float):
    """The committed benchmark's files with the new cell's added, and the
    tiny copy the harness runs."""
    src = tmp_path / "bench"
    shutil.copytree(BENCH_DIR, src, ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((src / "configs" / "fleet16k-svm.json").read_text())
    new = {
        "configs/svm-new.json": json.dumps({**cfg, "name": "svm-new"}),
        "traffic/sim-new.json": json.dumps({
            "driver": "sim_new", "loop": "closed", "T": 20, "eval_every": 10,
            "policy": "efhc", "check_answers": 1}),
        "drivers/sim_new.py": DRIVER,
        "reference/svm_new.py": REFERENCE.format(alpha_scale=alpha_scale),
        "limits/svm-new-cell.json": (src / "limits" / "fleet16k-ell.json").read_text(),
    }
    for rel, text in new.items():
        assert not (src / rel).exists(), rel
        (src / rel).write_text(text)
    tiny = tmp_path / "tiny"
    tiny.mkdir()
    (tiny / "svm-new-cell.json").write_text(json.dumps({
        "config": {"m": 32, "n_train": 128, "n_test": 32,
                   "matmul_operands": "float32"},
        "traffic": {"T": 5, "eval_every": 4}}))
    dest = tmp_path / "tiny_bench"
    dest.mkdir()
    return make_tiny(dest, {**BENCHMARK, "workloads": [CELL]}, src, tiny)


@pytest.mark.parametrize("alpha_scale,correct", [(1.0, True), (2.0, False)],
                         ids=["sound", "reference_steps_double"])
def test_cell_from_new_files_alone(tmp_path, alpha_scale, correct):
    """Sound, the new cell reads correct; with a fault planted in its
    reference (a learning rate twice the configuration's) it does not."""
    man = _add_cell(tmp_path, alpha_scale)
    res = run_tiny(man, CELL["name"])
    assert res["correct"] is correct, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
