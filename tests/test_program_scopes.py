"""The program's own names for its parts: ``jax.named_scope``s over the
compiled engine (``efhc.*``, carried in the HLO op metadata that a profile
attributes device time by) and ``jax.profiler.TraceAnnotation`` host spans
(``sim.*``, ``service.*``) on the profiler's clock."""
import contextlib
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.core import triggers
from repro.core.topology import make_process
from repro.data.loader import FederatedBatches
from repro.data.partition import by_labels
from repro.data.synthetic import image_dataset
from repro.fl import simulator

M, T, DIM, EVAL_EVERY = 8, 5, 16, 2
STEP = {"efhc.event1", "efhc.event2", "efhc.event3", "efhc.event4",
        "efhc.stats"}
ENGINE = STEP | {"efhc.eval", "efhc.init"}


def scopes_in(hlo_text: str) -> set[str]:
    return {s for op_name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for s in re.findall(r"efhc\.[a-z0-9_]+", op_name)}


@pytest.fixture(scope="module")
def fleet():
    x, y = image_dataset(160, seed=0, dim=DIM)
    x_test, y_test = image_dataset(24, seed=1, dim=DIM)
    parts = by_labels(y, M, 3)
    graph = make_process(M, "rgg", time_varying="edge_dropout", drop=0.3,
                         seed=0)
    sim = simulator.SimConfig(m=M, iters=T, dim=DIM, batch=4, seed=0,
                              mix_impl="sparse", trace="packed")
    eval_fn = simulator.make_eval_fn(sim, x_test, y_test)
    batches = lambda: FederatedBatches(x, y, parts, sim.batch, seed=2)
    return sim, graph, batches, eval_fn


def run_and_compile(sim, graph, batches, eval_fn) -> str:
    """Runs ``sim`` through ``simulator.run``; the compiled HLO text of the
    engine that run used (the cache hands the same jitted engine back)."""
    b = batches()
    simulator.run(sim, graph, b, eval_fn, eval_every=EVAL_EVERY)
    eng, _ = simulator._cached_engine(sim, graph, T=sim.iters,
                                      eval_every=EVAL_EVERY, x=b.x, y=b.y,
                                      eval_fn=eval_fn)
    idx = jnp.asarray(batches().stage(sim.iters))
    return eng.lower(triggers.policy_index(sim.policy),
                     jnp.asarray(sim.seed, jnp.int32), idx).compile().as_text()


@pytest.mark.parametrize("mix_impl,trace,expect", [
    ("sparse", "packed", ENGINE | {"efhc.ys"}),
    ("sharded", "summary", ENGINE | {"efhc.halo"}),
])
def test_fleet_engine_hlo_names_every_scope(fleet, mix_impl, trace, expect):
    sim, graph, batches, eval_fn = fleet
    sim = dataclasses.replace(sim, mix_impl=mix_impl, trace=trace)
    assert scopes_in(run_and_compile(sim, graph, batches, eval_fn)) == expect


def test_served_cnn_grid_hlo_names_every_scope():
    """The service's vmapped grid of cnn cells on the dense mix: the
    engine's scopes survive vmap, and ``efhc.ys`` packs the link trace."""
    spec = api.ScenarioSpec(m=4, model="cnn", dim=64, n_classes=4,
                            n_train=64, n_test=16, smooth=1, iters=3,
                            eval_every=2, batch=4, trace="packed",
                            seeds=(0, 1))
    svc = api.ScenarioService(max_cells=2)
    reports = api.serve([spec], service=svc)
    assert all(r.ok for r in reports)
    (grid, _), = svc._grids.values()
    n = len(spec.seeds)
    idx = jnp.zeros((n, spec.iters, spec.m, spec.batch), jnp.int32)
    txt = grid.lower(jnp.zeros(n, jnp.int32), jnp.arange(n, dtype=jnp.int32),
                     idx).compile().as_text()
    assert scopes_in(txt) == ENGINE | {"efhc.ys"}


def fresh_engine_hlo(sim, graph, batches, eval_fn) -> str:
    """Compiled HLO text of a newly built (not cached) engine."""
    b = batches()
    eng, _ = simulator.make_engine(sim, graph, T=sim.iters,
                                   eval_every=EVAL_EVERY, x=b.x, y=b.y,
                                   eval_fn=eval_fn)
    idx = jnp.asarray(b.stage(sim.iters))
    return jax.jit(eng).lower(0, jnp.asarray(0, jnp.int32),
                              idx).compile().as_text()


def without_metadata(hlo_text: str) -> str:
    """The program alone: no op metadata, nor the stack-frame tables that
    only metadata points into."""
    txt = re.sub(r",? metadata=\{[^}]*\}", "", hlo_text)
    return "\n".join(
        line for line in txt.splitlines()
        if not re.match(r"\s*\d+\s", line) and line.strip() not in
        ("FileNames", "FunctionNames", "FileLocations", "StackFrames"))


@pytest.mark.parametrize("mix_impl", ["sparse", "dense"])
def test_scopes_change_no_compiled_program(fleet, monkeypatch, mix_impl):
    """Scopes are op metadata: without them the engine compiles to the
    same program, op for op."""
    sim, graph, batches, eval_fn = fleet
    sim = dataclasses.replace(sim, mix_impl=mix_impl)
    scoped = fresh_engine_hlo(sim, graph, batches, eval_fn)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = fresh_engine_hlo(sim, graph, batches, eval_fn)
    assert scopes_in(scoped) and not scopes_in(plain)
    assert without_metadata(plain) == without_metadata(scoped)


def recorded_spans(tmp_path, fn) -> list[tuple[str, dict]]:
    """(name, arguments) of the program's host spans a profile of ``fn()``
    records, in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    evs = [e for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(("sim.", "service."))]
    return [(e.name, dict(e.stats))
            for e in sorted(evs, key=lambda e: e.start_ns)]


def test_simulator_run_spans_stage_launch_fetch(fleet, tmp_path):
    sim, graph, batches, eval_fn = fleet
    simulator.run(sim, graph, batches(), eval_fn, eval_every=EVAL_EVERY)
    got = recorded_spans(tmp_path, lambda: simulator.run(
        sim, graph, batches(), eval_fn, eval_every=EVAL_EVERY))
    assert got == [(n, {"m": M, "T": T})
                   for n in ("sim.stage", "sim.launch", "sim.fetch")]


def test_service_launch_spans_share_the_launch_id(tmp_path):
    spec = api.ScenarioSpec(m=6, dim=16, n_train=120, n_test=24, iters=4,
                            eval_every=2, seeds=(0, 1, 2))
    svc = api.ScenarioService(max_cells=4)
    api.serve([spec], service=svc)  # compiles outside the profile
    got = recorded_spans(tmp_path, lambda: api.serve(
        [spec, dataclasses.replace(spec, policy="gossip", seeds=(5,))],
        service=svc))
    phases = ("service.stage", "service.launch", "service.fetch",
              "service.report")
    launch = svc.stats().launches - 1
    assert got == [(n, {"launch_id": launch, "cells": 4}) for n in phases]
