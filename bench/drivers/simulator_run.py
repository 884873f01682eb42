"""Driver of ``repro.fl.simulator.run``: a closed loop of whole simulations.

Each call simulates the configured fleet for T iterations from a fresh run
seed and sampler seed (both traced by the engine, so no call recompiles),
with the policy the traffic names, through the entry users call.  The
sampler is the program's ``FederatedBatches``, whose staging the program's
``sim.stage`` span names in the trace.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import answers, scopes, work


class Cell:
    def __init__(self, config: dict, traffic: dict, rng: np.random.Generator):
        from repro.core.topology import EdgeList, GraphProcess
        from repro.fl.simulator import SimConfig, make_eval_fn

        self.config, self.traffic, self.rng = config, traffic, rng
        self.data = answers.make_data(config)
        self.edges = answers.fabric(config)
        m = config["m"]
        self.graph = GraphProcess(
            edges=EdgeList(u=self.edges[0], v=self.edges[1], m=m),
            kind="edge_dropout", drop=config["edge_dropout"],
            seed=config["dropout_seed"])
        self.sim = SimConfig(
            m=m, model=config["model"], n_classes=config["n_classes"],
            dim=config["dim"], batch=config["batch"], iters=traffic["T"],
            policy=traffic["policy"], r=config["r"], b_mean=config["b_mean"],
            sigma_n=config["sigma_n"], alpha0=config["alpha0"],
            mix_impl=answers.mix_impl(config), trace="summary")
        self.eval_fn = make_eval_fn(self.sim, self.data.x_test,
                                    self.data.y_test)

    def call(self) -> dict:
        """One simulation; returns {answers, dev_iters, scan_iters}."""
        from repro.data.loader import FederatedBatches
        from repro.fl import simulator

        seed, sample_seed = (answers.draw_seed(self.rng),
                             answers.draw_seed(self.rng))
        d = self.data
        batches = FederatedBatches(d.x, d.y, d.parts, self.sim.batch,
                                   seed=sample_seed)
        res = simulator.run(dataclasses.replace(self.sim, seed=seed),
                            self.graph, batches, self.eval_fn,
                            eval_every=self.traffic["eval_every"])
        with jax.profiler.TraceAnnotation("bench.result"):
            out = answers.extract(res)
        T = self.traffic["T"]
        return {"answers": [answers.Answer(seed, self.sim.policy,
                                           sample_seed, out)],
                "dev_iters": self.config["m"] * T, "scan_iters": T}

    def counters(self) -> dict:
        from repro.fl import simulator

        return {"engine_cache": simulator.engine_cache_stats().as_dict()}

    def iteration_work(self, calls: list[dict]) -> dict:
        """Operations and bytes of one scan iteration, the links used
        averaged over the window's answers."""
        links = np.mean([a.out["comm_count"].sum(axis=1).mean()
                         for call in calls for a in call["answers"]])
        return work.iteration_work(self.config, self.traffic, float(links))

    def op_scopes(self) -> dict:
        return scopes.live_op_scopes("engine")

    def reference(self, dtype=jnp.float32, precision=None) -> answers.Replay:
        return answers.Replay(answers.scenario(self.config, self.traffic,
                                               self.edges),
                              self.data, dtype, precision)
