"""Attribution of a traced window to the program's own names.

The program names each part of its compiled step with ``jax.named_scope``
(``efhc.event1`` ... ``efhc.event4``, ``efhc.stats``, ``efhc.eval``,
``efhc.ys``, ``efhc.init``, ``efhc.halo``) and its host phases with
``jax.profiler.TraceAnnotation`` spans (``sim.stage``, ``sim.launch``,
``sim.fetch``; ``service.stage``, ``service.launch``, ``service.fetch``,
``service.report``).  A traced run of the harness attributes its window
(``harness.run`` puts the result in ``Context.scopes``); on the trace
``bench.trace.load`` reads, over the same window (first ``bench.call``
start to last ``bench.call`` end):

* scopes    of a device op: for each of a stated tuple of scope prefixes
            (``PREFIXES``: the step's ``efhc.``; a model's own prefix
            after it), the innermost component with that prefix of the
            op_name metadata of the compiled HLO instruction the op is
            named after, transform wrappers such as ``transpose(jvp(...))``
            stripped; a fusion counts under the scopes its own metadata
            names.  The TPU's ``XLA Ops`` events carry no op_name, so after
            the window the cell's driver maps instruction names to scopes
            from the compiled text of the executables it ran
            (``live_op_scopes``), which a program loaded from the
            persistent cache has as well;
* scope_s   device self time per scope (``bench.trace.self_times``, ops
            inside the window), averaged over the chips.  An op counts once
            per prefix: the first prefix's scopes and ``unscoped`` (the rest
            of busy time) sum to ``busy_s``, and a later prefix's scopes
            name parts of that same time, so an op of a model's layer
            inside ``efhc.event4`` still counts toward Event 4;
* span_s    host time per program span: the union of its intervals
            clipped to the window;
* gaps      the first chip's idle gaps, each named after the innermost
            program span over its midpoint.

    python3 -m bench.scopes --workload <name> --seed <n> --seconds <s>

runs one cell as ``bench/run.py ... --trace 1`` does, prints the
attribution's per-scope and per-span table to standard error and, as the
last line of standard output, the run's result object with ``scopes``
added.  It exits non-zero where the first JAX device is not a TPU.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

from bench import trace as trace_mod

PREFIXES = ("efhc.",)
SPAN_PREFIXES = ("sim.", "service.")
STAGE_SPANS = ("sim.stage", "service.stage")
UNSCOPED = "unscoped"
# HLO text: an instruction's name and the op_name of its metadata
HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?op_name="([^"]*)"')


def _scope_re(prefix: str) -> re.Pattern:
    return re.compile(r"(?<![A-Za-z0-9_.])" + re.escape(prefix) + r"[A-Za-z0-9_]+")


def scopes_of(op_name: str, prefixes: tuple[str, ...] = PREFIXES
              ) -> tuple[str | None, ...]:
    """'jit(engine)/while/body/transpose(jvp(efhc.event4))/dot' ->
    ('efhc.event4',): per prefix, the innermost component with it, or None.

    Scopes of one prefix do not nest in the step, except ``efhc.halo``,
    which counts a halo exchange under itself also where an Event's masks
    call it."""
    out = []
    for p in prefixes:
        found = _scope_re(p).findall(op_name)
        out.append(found[-1] if found else None)
    return tuple(out)


def hlo_scopes(text: str, prefixes: tuple[str, ...] = PREFIXES
               ) -> dict[str, tuple[str | None, ...]]:
    """Instruction name -> ``scopes_of`` its op_name, over the instructions
    of compiled HLO text whose op_name metadata names a scope."""
    out = {}
    for line in text.splitlines():
        m = HLO_LINE.match(line)
        if m:
            sc = scopes_of(m.group(2), prefixes)
            if any(sc):
                out[m.group(1)] = sc
    return out


def live_op_scopes(module: str, prefixes: tuple[str, ...] = PREFIXES
                   ) -> dict[str, tuple[str | None, ...]]:
    """``hlo_scopes`` of the compiled text of every executable alive in the
    process whose module name contains ``module``.  An instruction name
    that two such executables map to different scopes is left out: the
    trace names ops by instruction alone."""
    import jax

    out: dict[str, tuple] = {}
    clash: set[str] = set()
    for exe in jax.devices()[0].client.live_executables():
        for mod in exe.hlo_modules():
            if module not in mod.name:
                continue
            for op, sc in hlo_scopes(mod.to_string(), prefixes).items():
                if out.setdefault(op, sc) != sc:
                    clash.add(op)
    return {k: v for k, v in out.items() if k not in clash}


@dataclasses.dataclass
class Attributed:
    window_s: float
    busy_s: float
    scope_s: dict[str, float]  # device self time per scope, and unscoped
    span_s: dict[str, float]  # host time per program span name
    stage_s: float  # union of the program's staging spans
    gaps: list[tuple[str, float]]  # longest first
    ops: list[tuple[str, str, float]]  # (op, first scope, self time), largest first

    @property
    def covered(self) -> float:
        """Share of busy time the first prefix's scopes name."""
        if not self.busy_s:
            return 0.0
        return 1.0 - self.scope_s[UNSCOPED] / self.busy_s


def _innermost(spans: list[trace_mod.Event], t: float) -> str:
    inner = [sp for sp in spans if sp.start <= t <= sp.end]
    return (min(inner, key=lambda sp: sp.end - sp.start).name
            if inner else "no program span")


def attribute(tr: trace_mod.Trace, op_scopes: dict[str, tuple[str | None, ...]],
              *, top: int = 10) -> Attributed:
    """Scope and span times of the window; ``op_scopes`` maps op names to
    their scopes, one per prefix (``hlo_scopes``)."""
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
    scope_s, named = defaultdict(float), 0.0
    for name, own in per_op.items():
        scopes = op_scopes.get(name, ())
        for sc in scopes:
            if sc:
                scope_s[sc] += own
        if scopes and scopes[0]:
            named += own
    scope_s = dict(sorted(scope_s.items()))
    scope_s[UNSCOPED] = busy - named
    ops = sorted(((k, (op_scopes.get(k) or (None,))[0] or UNSCOPED, v)
                  for k, v in per_op.items()), key=lambda o: -o[2])

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
    named_gaps = sorted(((_innermost(program, (s + f) / 2), f - s) for s, f in gaps),
                        key=lambda g: -g[1])
    return Attributed(
        window_s=hi - lo, busy_s=busy, scope_s=scope_s,
        span_s={k: covered(v) for k, v in sorted(by_name.items())},
        stage_s=covered([iv for k in STAGE_SPANS for iv in by_name.get(k, [])]),
        gaps=named_gaps[:top], ops=ops[:top])


def scan_iters(ctx) -> int:
    return sum(c["scan_iters"] for c in ctx.calls)


def device_ms(ctx, names: tuple[str, ...]) -> float | None:
    """Device self time of the named scopes per scan iteration, in ms,
    counted as ``step_device_ms`` counts its busy time; None where the run
    was not attributed or the window ran none of them."""
    att, iters = ctx.scopes, scan_iters(ctx)
    if att is None or not iters or not any(k in att.scope_s for k in names):
        return None
    return 1000.0 * sum(att.scope_s.get(k, 0.0) for k in names) / iters


def table(att: Attributed, iters: int) -> list[str]:
    """The per-scope and per-span rows, for standard error."""
    rows = [f"scopes over {att.busy_s:.6f}s busy of {att.window_s:.6f}s window,"
            f" {iters} scan iterations ({100 * att.covered:.3f}% named)"]
    for k, v in sorted(att.scope_s.items(), key=lambda kv: -kv[1]):
        per = f"{1000 * v / iters:.6f} ms/iter" if iters else ""
        rows.append(f"scope {k:<14} {v:.6f}s {100 * v / att.busy_s if att.busy_s else 0:.3f}% {per}")
    for k, v in sorted(att.span_s.items(), key=lambda kv: -kv[1]):
        rows.append(f"span {k:<15} {v:.6f}s {100 * v / att.window_s:.3f}% of window")
    rows += [f"op {name} {scope} {s:.6f}s" for name, scope, s in att.ops]
    rows += [f"gap {name} {s:.6f}s" for name, s in att.gaps]
    return rows


def run_attributed(manifest, workload: str, seed: int, seconds: float, *,
                   devices, device_kind: str, t0: float, log=print) -> dict:
    """One traced run of a cell through ``bench.harness.run``; logs the
    table of its attribution and returns the run's result object with
    ``scopes`` added."""
    from bench import harness

    held = {}
    res = harness.run(workload, seed, seconds, True, manifest=manifest,
                      devices=devices, device_kind=device_kind, t0=t0, log=log,
                      on_context=lambda ctx: held.update(ctx=ctx))
    ctx = held["ctx"]
    att, iters = ctx.scopes, scan_iters(ctx)
    for row in table(att, iters):
        log(row)
    res["scopes"] = {
        "scan_iters": iters, "covered": att.covered, "scope_s": att.scope_s,
        "span_s": att.span_s, "gaps": att.gaps, "ops": att.ops}
    return res


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys
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
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from bench import harness

    harness.use_cache(root)

    def err(msg):
        print(msg, file=sys.stderr, flush=True)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        err(f"bench: the first JAX device is {devs[0].platform!r}, not a TPU")
        return 1
    manifest = harness.Manifest.load(root / "BENCHMARK.json")
    chips = manifest.workload(args.workload)["chips"]
    res = run_attributed(manifest, args.workload, args.seed, args.seconds,
                         devices=devs[:chips], device_kind=devs[0].device_kind,
                         t0=t0, log=err)
    res.pop("peak_bytes", None)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
