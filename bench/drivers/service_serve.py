"""Driver of ``repro.api.serve`` with one resident ``ScenarioService``.

Each call is one round of a closed loop: one request per policy the
traffic names, each with fresh seeds, submitted together and served
(compatible signatures, so the service batches every cell into one
vmapped launch).  The dataset is the benchmark's, handed to the service
through its provider hook; the service builds the fabric from the spec.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import answers, scopes, work


class Cell:
    def __init__(self, config: dict, traffic: dict, rng: np.random.Generator):
        from repro import api

        self.config, self.traffic, self.rng = config, traffic, rng
        self.data = answers.make_data(config)
        d = self.data
        ds = api.Dataset(d.x, d.y, d.parts, d.x_test, d.y_test)
        self.spec = api.ScenarioSpec(
            m=config["m"], topology=config["fabric"],
            time_varying="edge_dropout", drop=config["edge_dropout"],
            graph_seed=config["fabric_seed"], model=config["model"],
            dim=config["dim"], n_classes=config["n_classes"],
            n_train=config["n_train"], n_test=config["n_test"],
            data_seed=config["data_seed"],
            labels_per_device=config["labels_per_device"],
            smooth=config["smooth"], r=config["r"], b_mean=config["b_mean"],
            sigma_n=config["sigma_n"], alpha0=config["alpha0"],
            batch=config["batch"], iters=traffic["T"],
            mix_impl=answers.mix_impl(config), trace="summary",
            eval_every=traffic["eval_every"],
            sample_seed=traffic["sample_seed"])
        self.service = api.ScenarioService(lambda spec: ds,
                                           max_cells=traffic["max_cells"])

    def call(self) -> dict:
        from repro import api

        n = self.traffic["seeds_per_request"]
        specs = [dataclasses.replace(
            self.spec, policy=p,
            seeds=tuple(answers.draw_seed(self.rng) for _ in range(n)))
            for p in self.traffic["policies"]]
        reports = api.serve(specs, service=self.service)
        with jax.profiler.TraceAnnotation("bench.result"):
            outs = []
            for rep in reports:
                if not rep.ok:
                    raise RuntimeError(f"request {rep.request_id}: {rep.error}")
                for s in rep.spec.seeds:
                    outs.append(answers.Answer(
                        s, rep.spec.policy, self.spec.sample_seed + s,
                        answers.extract(rep.result(s))))
        launches = {r.launch_id: r for r in reports}.values()
        T = self.traffic["T"]
        return {"answers": outs, "dev_iters": self.config["m"] * T * len(outs),
                "scan_iters": T * len(launches),
                "stage_s": sum(r.stage_s for r in launches)}

    def counters(self) -> dict:
        return {"service": self.service.stats().as_dict()}

    def iteration_work(self, calls: list[dict]) -> dict:
        """Operations and bytes of one scan iteration of a launch: the
        launch's real cells, each at the links it used."""
        per_launch = []
        for call in calls:
            links = [a.out["comm_count"].sum(axis=1).mean() for a in call["answers"]]
            launches = call["scan_iters"] // self.traffic["T"]
            per_launch.append((float(np.sum(links)) / launches,
                               len(call["answers"]) / launches))
        links, cells = np.mean(per_launch, axis=0)
        one = work.iteration_work(self.config, self.traffic, float(links / cells))
        return {k: v * cells for k, v in one.items()}

    def op_scopes(self) -> dict:
        return scopes.live_op_scopes("engine")

    def reference(self, dtype=jnp.float32, precision=None) -> answers.Replay:
        return answers.Replay(answers.scenario(self.config, self.traffic,
                                               answers.fabric(self.config)),
                              self.data, dtype, precision)
