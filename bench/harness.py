"""The benchmark's harness: finds a cell's files by name and runs it.

A cell (``workloads`` in BENCHMARK.json) names a configuration and a
traffic mix.  Everything of one kind sits in a file of its own under the
benchmark's directory, found by the name:

    configs/<config>.json     the deployment (sizes, fabric, data, source)
    traffic/<traffic>.json    the mix, naming the driver it runs through
    drivers/<driver>.py       one per entry point the window drives
    metrics/<metric>.py       one reader per metric: read(ctx) -> float | None
    limits/<workload>.json    the limits of the comparison that decides
                              ``correct``, with the readings behind them

A driver's ``Cell(config, traffic, rng)`` holds the cell's data and
program and gives:

    call()              one closed-loop call: {"answers", "dev_iters",
                        "scan_iters", ...}; each answer has ``out``, the
                        channels ``bench.check.compare`` reads
    counters()          the program's counters, logged after the window
    iteration_work(calls)  operations and bytes of one scan iteration
    op_scopes()         after the window, instruction name -> scopes of the
                        executables it ran (``bench.scopes.live_op_scopes``)
    reference(dtype=jnp.float32, precision=None)
                        the cell's plain reference: ``replay(answer)`` gives
                        the channels ``check.compare`` reads, following the
                        answer's decisions; ``answer(seed, policy,
                        sample_seed)`` puts the reference in the program's
                        place (at ``dtype=jnp.bfloat16``, the control)

A run: set-up (data, fabric, program, one warm-up call at the window's
shapes), the measured window of closed-loop calls, the device's memory
peak, the trace reduction and its attribution to the program's scopes and
spans (``--trace 1``), then the check of a sample of the window's answers
against the cell's reference.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import check

BENCH_DIR = Path(__file__).resolve().parent
# Each engine carries its training set as a constant, so the fleet's
# executable is about 250 MB: a cache that refused it would compile in
# every run.
CACHE_CAP_BYTES = 2**30


def use_cache(root: Path) -> None:
    """JAX's persistent compilation cache where JAX_COMPILATION_CACHE_DIR
    says, else in ``<root>/.jax_cache``; every program cached, an entry and
    the whole cache of up to CACHE_CAP_BYTES unless a larger cap is set."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cap = jax.config.jax_compilation_cache_max_size  # -1: no cap, 0: no cache
    if 0 < cap < CACHE_CAP_BYTES:
        jax.config.update("jax_compilation_cache_max_size", CACHE_CAP_BYTES)


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Manifest:
    """BENCHMARK.json and the directory its named files live in."""

    data: dict
    root: Path = BENCH_DIR

    @classmethod
    def load(cls, path: Path, root: Path = BENCH_DIR) -> "Manifest":
        return cls(json.loads(Path(path).read_text()), Path(root))

    def _named(self, key: str, name: str) -> dict:
        for entry in self.data[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"no {key} entry named {name!r}")

    def workload(self, name: str) -> dict:
        return self._named("workloads", name)

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.root / kind / f"{name}.json").read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, workload: str) -> dict:
        return self._json("limits", workload)["limits"]

    def compared_iterations(self, workload: str) -> int | dict | None:
        """How many leading iterations the weight-following numbers
        compare: one count, a count per number, or None for all."""
        return self._json("limits", workload).get("iterations")

    def driver(self, name: str):
        return _load_module(self.root / "drivers" / f"{name}.py",
                            f"bench_driver_{name}")

    def reader(self, metric: str):
        return _load_module(self.root / "metrics" / f"{metric}.py",
                            f"bench_metric_{metric.replace('.', '_')}").read

    def metrics(self, workload: str, traced: bool) -> list[dict]:
        """The metrics a run of ``workload`` reports: its end-to-end ones
        untraced, its per-layer ones traced."""
        e2e = [m for m in self.data["end_to_end"]
               if workload in m.get("workloads", [workload])]
        if not traced:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if workload in m.get("workloads", [workload] if m["moves"] in names else [])]


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""

    setup_s: float
    window_s: float
    calls: list[dict]
    peak_bytes: list[int] | None
    trace: object | None  # bench.trace.Reduced
    work: dict  # operations and bytes of one scan iteration
    peaks: dict  # the device's published peaks
    scopes: object | None = None  # bench.scopes.Attributed, traced runs


class _CompileCounter:
    """Counts XLA compilations while open (jax.monitoring events)."""

    def __enter__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def _listen(self, event, duration, **kw):
        if "backend_compile" in event:
            self.n += 1

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)


# The TPU runtime keeps buffers (arrays: parameters, data, a call's inputs
# and outputs) and the compiled programs' temporaries (the scan carry) in
# two pools and counts the peak of each apart.
PEAK_COUNTERS = ("peak_bytes_in_use", "peak_bytes_reserved")


def _memory_peaks(devices, log=print) -> list[int] | None:
    """Per chip, the peak of the buffers plus the peak of the programs'
    temporaries."""
    out = []
    for d in devices:
        st = d.memory_stats()
        if not st:
            return None
        log(f"memory {d.id}: " + json.dumps({k: st[k] for k in sorted(st)}))
        out.append(sum(int(st[k]) for k in PEAK_COUNTERS))
    return out


def _span(calls: list[dict]) -> float:
    return calls[-1]["t"][1] - calls[0]["t"][0]


def _finite(x: float):
    return x if np.isfinite(x) else None


def run(name: str, seed: int, seconds: float, traced: bool, *,
        manifest: Manifest, devices, device_kind: str, t0: float,
        log=print, on_context=None) -> dict:
    """One run of one cell; returns the result object (without the
    device fields the caller adds).  ``on_context`` is handed the metric
    readers' Context."""
    import jax

    from bench import peaks as peaks_mod
    from bench import scopes as scopes_mod
    from bench import trace as trace_mod

    wl = manifest.workload(name)
    config, traffic = manifest.config(wl["config"]), manifest.traffic(wl["traffic"])
    limits = manifest.limits(name)
    iters = manifest.compared_iterations(name)
    rng = np.random.default_rng(seed)
    log(f"imports {time.perf_counter() - t0:.3f}s")
    cell = manifest.driver(traffic["driver"]).Cell(config, traffic, rng)
    log(f"data and fabric {time.perf_counter() - t0:.3f}s")
    cell.call()  # warm-up: compiles every shape the window runs
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f}s")

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    calls = []
    try:
        with _CompileCounter() as compiles:
            # whole calls until the window is nearest ``seconds``: stop once
            # half a mean call more would pass it
            while not calls or _span(calls) * (1 + 0.5 / len(calls)) < seconds:
                ts = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.call"):
                    rec = cell.call()
                rec["t"] = (ts, time.perf_counter())
                calls.append(rec)
    finally:
        if traced:
            jax.profiler.stop_trace()
    window_s = _span(calls)
    log(f"window {window_s:.3f}s, {len(calls)} calls, "
        f"{compiles.n} compilations inside it; counters "
        f"{json.dumps(cell.counters())}")
    peak = _memory_peaks(devices, log)

    reduced = attributed = None
    if traced:
        t_trace = time.perf_counter()
        tr = trace_mod.load(tdir, len(devices))
        reduced = trace_mod.reduce(tr)
        attributed = scopes_mod.attribute(tr, cell.op_scopes())
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace read and attributed {time.perf_counter() - t_trace:.3f}s, "
            f"{100 * attributed.covered:.3f}% of busy time scoped")
    ctx = Context(setup_s=setup_s, window_s=window_s, calls=calls,
                  peak_bytes=peak, trace=reduced,
                  work=cell.iteration_work(calls),
                  peaks=peaks_mod.peaks(device_kind) if device_kind else {},
                  scopes=attributed)
    metrics = {}
    for m in manifest.metrics(name, traced):
        v = manifest.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if on_context is not None:
        on_context(ctx)

    # the check: a sample of the window's answers against the reference
    done = [a for c in calls for a in c["answers"]]
    pick = rng.choice(len(done), size=min(traffic["check_answers"], len(done)),
                      replace=False)
    sample = [done[i] for i in sorted(pick)]
    del calls, ctx
    t_check = time.perf_counter()
    ref = cell.reference()
    readings = [check.compare(a.out, ref.replay(a), iters) for a in sample]
    judged = check.judge(check.worst(readings), limits)
    log(f"check of {len(sample)} answers {time.perf_counter() - t_check:.3f}s")
    failed = sum(not (np.isfinite(a.out["loss"]).all()
                      and np.isfinite(a.out["consensus_err"]).all()) for a in done)
    result = {"correct": all(j["ok"] for j in judged),
              "attempted": len(done), "failed": int(failed), "metrics": metrics}
    if reduced is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in reduced.device_ops],
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps]}
        result["busy_s"], result["window_s"] = reduced.busy_s, reduced.window_s
    result["peak_bytes"] = peak
    result["checks"] = [{"name": j["name"], "value": _finite(j["value"]),
                         "limit": j["limit"]} for j in judged]
    return result
