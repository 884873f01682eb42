"""Attribution of a traced window to the program's own names.

The program names each part of its compiled step with ``jax.named_scope``
(``efhc.event1`` ... ``efhc.event4``, ``efhc.stats``, ``efhc.eval``,
``efhc.ys``, ``efhc.init``, ``efhc.halo``) and its host phases with
``jax.profiler.TraceAnnotation`` spans (``sim.stage``, ``sim.launch``,
``sim.fetch``; ``service.stage``, ``service.launch``, ``service.fetch``,
``service.report``).  On the trace ``bench.trace.load`` reads, over the
same window (first ``bench.call`` start to last ``bench.call`` end):

* scope     of a device op: the innermost ``efhc.*`` component of the
            op_name metadata of the compiled HLO instruction the op is
            named after, transform wrappers such as
            ``transpose(jvp(...))`` stripped; a fusion counts under the
            scope its own metadata names.  The TPU's ``XLA Ops`` events
            carry no op_name (their stats are ``device_offset_ps``,
            ``device_duration_ps`` and ``Time Scale Multiplier``), so the
            names come from the HLO text XLA dumps as it compiles;
* scope_s   device self time per scope (``bench.trace.self_times``, ops
            inside the window), averaged over the chips; ``unscoped`` is
            the rest of busy time, so the scopes sum to ``busy_s``;
* span_s    host time per program span: the union of its intervals
            clipped to the window;
* gaps      the first chip's idle gaps, each named after the innermost
            program span over its midpoint.

    python3 -m bench.scopes --workload <name> --seed <n> --seconds <s>

runs one cell as ``bench/run.py ... --trace 1`` does, with the compiled
engines' HLO dumped, attributes the window, prints the per-scope and
per-span table to standard error and, as the last line of standard
output, the run's result object with ``scopes`` (the attribution and the
per-layer numbers ``layer_metrics`` computes) added.  It exits non-zero
where the first JAX device is not a TPU.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

from bench import trace as trace_mod

SCOPE = re.compile(r"efhc\.[A-Za-z0-9_]+")
SPAN_PREFIXES = ("sim.", "service.")
STAGE_SPANS = ("sim.stage", "service.stage")
UNSCOPED = "unscoped"
# HLO text: an instruction's name and the op_name of its metadata
HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?op_name="([^"]*)"')


def scope_of(op_name: str) -> str | None:
    """'jit(engine)/while/body/transpose(jvp(efhc.event4))/dot' -> 'efhc.event4'.

    The innermost ``efhc.*`` component: scopes do not nest in the step,
    except ``efhc.halo``, which counts a halo exchange under itself also
    where an Event's masks call it."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def hlo_scopes(text: str) -> dict[str, str]:
    """Instruction name -> scope, over the instructions of compiled HLO
    text whose op_name metadata names one."""
    out = {}
    for line in text.splitlines():
        m = HLO_LINE.match(line)
        if m:
            sc = scope_of(m.group(2))
            if sc is not None:
                out[m.group(1)] = sc
    return out


def hlo_dir_scopes(path: str) -> dict[str, str]:
    """``hlo_scopes`` over the optimized modules XLA dumped under ``path``
    (``--xla_dump_to`` with ``--xla_dump_hlo_as_text``)."""
    out: dict[str, str] = {}
    for f in sorted(glob.glob(os.path.join(path, "*after_optimizations.txt"))):
        with open(f) as fh:
            out.update(hlo_scopes(fh.read()))
    return out


@dataclasses.dataclass
class Attributed:
    window_s: float
    busy_s: float
    scope_s: dict[str, float]  # device self time per scope, and unscoped
    span_s: dict[str, float]  # host time per program span name
    stage_s: float  # union of the program's staging spans
    gaps: list[tuple[str, float]]  # longest first
    ops: list[tuple[str, str, float]]  # (op, scope, self time), largest first

    @property
    def covered(self) -> float:
        """Share of busy time the program's scopes name."""
        if not self.busy_s:
            return 0.0
        return 1.0 - self.scope_s[UNSCOPED] / self.busy_s


def _innermost(spans: list[trace_mod.Event], t: float) -> str:
    inner = [sp for sp in spans if sp.start <= t <= sp.end]
    return (min(inner, key=lambda sp: sp.end - sp.start).name
            if inner else "no program span")


def attribute(tr: trace_mod.Trace, op_scope: dict[str, str],
              *, top: int = 10) -> Attributed:
    """Scope and span times of the window; ``op_scope`` maps op names to
    scopes (``hlo_scopes``)."""
    calls = [e for e in tr.host_spans if e.name == trace_mod.CALL_SPAN]
    if not calls:
        raise ValueError("the trace holds no bench.call span")
    lo = min(e.start for e in calls)
    hi = max(e.end for e in calls)
    n = max(len(tr.device_ops), 1)
    busy, per_op = 0.0, defaultdict(float)
    for ops in tr.device_ops:
        iv = trace_mod.union(trace_mod._clip([(e.start, e.end) for e in ops],
                                             lo, hi))
        busy += sum(e - s for s, e in iv) / n
        for name, s, f, own in trace_mod.self_times(ops):
            if s >= lo and f <= hi:
                per_op[name] += own / n
    scope_s = defaultdict(float)
    for name, own in per_op.items():
        if op_scope.get(name):
            scope_s[op_scope[name]] += own
    scope_s = dict(sorted(scope_s.items()))
    scope_s[UNSCOPED] = busy - sum(scope_s.values())
    ops = sorted(((k, op_scope.get(k) or UNSCOPED, v) for k, v in per_op.items()),
                 key=lambda o: -o[2])

    program = [e for e in tr.host_spans if e.name.startswith(SPAN_PREFIXES)]
    by_name = defaultdict(list)
    for e in program:
        by_name[e.name].append((e.start, e.end))

    def covered(iv):
        return sum(f - s for s, f in trace_mod.union(trace_mod._clip(iv, lo, hi)))

    first = trace_mod.union(trace_mod._clip(
        [(e.start, e.end) for e in tr.device_ops[0]], lo, hi))
    edges = [lo] + [t for iv in first for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = sorted(((_innermost(program, (s + f) / 2), f - s) for s, f in gaps),
                   key=lambda g: -g[1])
    return Attributed(
        window_s=hi - lo, busy_s=busy, scope_s=scope_s,
        span_s={k: covered(v) for k, v in sorted(by_name.items())},
        stage_s=covered([iv for k in STAGE_SPANS for iv in by_name.get(k, [])]),
        gaps=named[:top], ops=ops[:top])


# per-layer numbers: device ms per scan iteration (counted as
# ``step_device_ms`` counts them) of the scopes each sums, and the share of
# the window the program spent staging
DEVICE_METRICS = {
    "graph_trigger_device_ms": ("efhc.event1", "efhc.event2"),
    "mix_device_ms": ("efhc.event3",),
    "local_step_device_ms": ("efhc.event4",),
    "eval_device_ms": ("efhc.eval",),
}


def layer_metrics(att: Attributed, scan_iters: int) -> dict[str, float]:
    out = {name: 1000.0 * sum(att.scope_s.get(s, 0.0) for s in scopes) / scan_iters
           for name, scopes in DEVICE_METRICS.items()} if scan_iters else {}
    out["host_stage_share"] = 100.0 * att.stage_s / att.window_s
    return out


def table(att: Attributed, scan_iters: int) -> list[str]:
    """The per-scope and per-span rows, for standard error."""
    rows = [f"scopes over {att.busy_s:.6f}s busy of {att.window_s:.6f}s window,"
            f" {scan_iters} scan iterations ({100 * att.covered:.3f}% named)"]
    for k, v in sorted(att.scope_s.items(), key=lambda kv: -kv[1]):
        per = f"{1000 * v / scan_iters:.6f} ms/iter" if scan_iters else ""
        rows.append(f"scope {k:<14} {v:.6f}s {100 * v / att.busy_s if att.busy_s else 0:.3f}% {per}")
    for k, v in sorted(att.span_s.items(), key=lambda kv: -kv[1]):
        rows.append(f"span {k:<15} {v:.6f}s {100 * v / att.window_s:.3f}% of window")
    rows += [f"op {name} {scope} {s:.6f}s" for name, scope, s in att.ops]
    rows += [f"gap {name} {s:.6f}s" for name, s in att.gaps]
    return rows


def run_attributed(manifest, workload: str, seed: int, seconds: float, *,
                   devices, device_kind: str, t0: float, log=print,
                   hlo_dir: str | None = None) -> dict:
    """One traced run of a cell through ``bench.harness.run``, its window
    attributed before the harness removes the trace; returns the run's
    result object with ``scopes`` added.  ``hlo_dir``: where XLA dumped the
    compiled engines' HLO text (``hlo_dir_scopes``); without it no op is
    scoped."""
    from bench import harness

    held = {}
    bench_load = trace_mod.load

    def keep(path, n):
        held["trace"] = bench_load(path, n)
        return held["trace"]

    class Recording(harness.Manifest):
        """Keeps the context the metric readers are handed."""

        def reader(self, metric):
            read = super().reader(metric)

            def recorded(ctx):
                held["ctx"] = ctx
                return read(ctx)
            return recorded

    trace_mod.load = keep
    try:
        res = harness.run(workload, seed, seconds, True,
                          manifest=Recording(manifest.data, manifest.root),
                          devices=devices, device_kind=device_kind, t0=t0,
                          log=log)
    finally:
        trace_mod.load = bench_load
    op_scope = hlo_dir_scopes(hlo_dir) if hlo_dir else {}
    ctx = held["ctx"]
    iters = sum(c["scan_iters"] for c in ctx.calls)
    att = attribute(held["trace"], op_scope)
    for row in table(att, iters):
        log(row)
    res["scopes"] = {
        "hlo_ops_scoped": len(op_scope), "scan_iters": iters,
        "device_iters_per_s": sum(c["dev_iters"] for c in ctx.calls) / ctx.window_s,
        "covered": att.covered, "scope_s": att.scope_s, "span_s": att.span_s,
        "gaps": att.gaps, "ops": att.ops, "metrics": layer_metrics(att, iters)}
    return res


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import sys
    import tempfile
    import time
    from pathlib import Path

    t0 = time.perf_counter()
    root = Path(__file__).resolve().parents[1]
    ap = argparse.ArgumentParser(
        description="One traced run of a cell, attributed to the program's "
                    "scopes and spans.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(root / "src"))
    # XLA writes the compiled engines' HLO text here as it compiles them; a
    # program read from the persistent cache would not be, so none is read
    hlo_dir = tempfile.mkdtemp(prefix="bench_hlo_")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" --xla_dump_to={hlo_dir}"
        " --xla_dump_hlo_as_text --xla_dump_hlo_module_re=.*engine.*")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from bench import harness

    jax.config.update("jax_enable_compilation_cache", False)

    def err(msg):
        print(msg, file=sys.stderr, flush=True)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        err(f"bench: the first JAX device is {devs[0].platform!r}, not a TPU")
        return 1
    manifest = harness.Manifest.load(root / "BENCHMARK.json")
    chips = manifest.workload(args.workload)["chips"]
    try:
        res = run_attributed(manifest, args.workload, args.seed, args.seconds,
                             devices=devs[:chips],
                             device_kind=devs[0].device_kind, t0=t0, log=err,
                             hlo_dir=hlo_dir)
    finally:
        shutil.rmtree(hlo_dir, ignore_errors=True)
    res.pop("peak_bytes", None)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
