"""Attribution of a traced window to the program's named scopes and host
spans (``bench.scopes``) and the per-layer metrics that read it, on
synthetic traces, executables compiled here and tiny runs of the
benchmark's cells."""
from __future__ import annotations

import pytest

from bench import scopes as sc
from bench import trace as tr
from bench.harness import BENCH_DIR, Context, Manifest

op = span = tr.Event
READERS = Manifest.load(BENCH_DIR.parent / "BENCHMARK.json")


@pytest.mark.parametrize("op_name,scope", [
    ("jit(engine)/while/body/efhc.event3/mul", "efhc.event3"),
    ("jit(engine)/vmap()/while/body/closed_call/transpose(jvp(efhc.event4))/dot_general",
     "efhc.event4"),
    ("jit(engine)/while/body/jvp(efhc.event4)/tanh", "efhc.event4"),
    ("jit(engine)/shard_map/efhc.event1/efhc.halo/all_gather", "efhc.halo"),
    ("jit(engine)/while/body/dynamic_update_slice", None),
    ("reduce_sum", None),
])
def test_scope_is_the_innermost_efhc_component(op_name, scope):
    assert sc.scopes_of(op_name) == (scope,)


@pytest.mark.parametrize("op_name,scopes", [
    ("jit(engine)/while/body/efhc.event4/mla.attn/dot_general",
     ("efhc.event4", "mla.attn", None)),
    ("jit(engine)/while/body/transpose(jvp(efhc.event4))/transpose(jvp(moe.experts))/mla.x/dot",
     ("efhc.event4", "mla.x", "moe.experts")),
    ("jit(engine)/moe.router/efhc.event2/sub", ("efhc.event2", None, "moe.router")),
    ("jit(engine)/notefhc.event3/mla_attn/add", (None, None, None)),
])
def test_scopes_per_prefix(op_name, scopes):
    """One scope per stated prefix, each the innermost of its own prefix,
    whichever way the program nests them."""
    assert sc.scopes_of(op_name, ("efhc.", "mla.", "moe.")) == scopes


def test_hlo_text_maps_instructions_to_scopes():
    text = "\n".join([
        'HloModule jit_engine, entry_computation_layout={()->f32[]}',
        '  %p = f32[8]{0} parameter(0), metadata={op_name="w"}',
        '  %fusion.364 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'calls=%fused, metadata={op_name="jit(engine)/while/body/efhc.event3/mul" '
        'source_file="efhc.py" source_line=3}',
        '  ROOT %reduce-window.152 = f32[4]{0} reduce-window(%p), '
        'metadata={op_name="jit(engine)/while/body/efhc.eval/reduce_window_sum"}',
        '  %copy.7 = f32[8]{0} copy(%p)',
    ])
    want = {"fusion.364": ("efhc.event3",), "reduce-window.152": ("efhc.eval",)}
    assert sc.hlo_scopes(text) == want


def _compiled_engine(scope: str):
    import jax
    import jax.numpy as jnp

    def probe_engine(a):
        with jax.named_scope(scope):
            with jax.named_scope("mla.attn"):
                return jnp.sin(a) * 3.0

    f = jax.jit(probe_engine)
    f(jnp.ones((8,))).block_until_ready()
    return f


def test_live_executables_map_the_engine_ops():
    """The compiled text of the executables alive in the process names the
    ops the trace names; an instruction that two such executables scope
    differently is left out (the module names here are this test's own)."""
    keep = [_compiled_engine("efhc.event3")]
    got = sc.live_op_scopes("probe_engine", ("efhc.", "mla."))
    assert ("efhc.event3", "mla.attn") in set(got.values())
    keep.append(_compiled_engine("efhc.event2"))
    both = sc.live_op_scopes("probe_engine", ("efhc.", "mla."))
    assert not {op for op, s in got.items() if s == ("efhc.event3", "mla.attn")} & set(both)
    assert not sc.live_op_scopes("no such module")


def window_trace():
    """One chip over a 10 s window of two calls.  A while (unscoped) holds
    the Event 3 and 4 ops of its body.  The host stages inside each call,
    once under a benchmark span."""
    host = [span("bench.call", 0.0, 6.0), span("sim.stage", 0.0, 2.0),
            span("bench.stage", 0.5, 2.0), span("sim.launch", 2.0, 2.2),
            span("sim.fetch", 2.2, 6.0),
            span("bench.call", 6.0, 10.0), span("sim.stage", 6.0, 7.0),
            span("sim.launch", 7.0, 7.1), span("sim.fetch", 7.1, 9.4),
            span("bench.result", 9.4, 10.0)]
    ops = [op("while.1", 2.0, 5.0), op("fusion.1", 2.5, 3.5),
           op("fusion.2", 3.5, 4.5), op("reduce-window.1", 5.0, 5.5),
           op("fusion.9", 7.0, 8.0), op("fusion.1", 8.0, 9.0),
           op("outside", 11.0, 12.0)]
    return tr.Trace(device_ops=[ops], host_spans=host)


# the scopes the compiled HLO's metadata gives; the while has none
HLO_MAP = {"fusion.1": ("efhc.event3",), "fusion.2": ("efhc.event4",),
           "reduce-window.1": ("efhc.eval",), "fusion.9": ("efhc.event1",),
           "fusion.20": ("efhc.stats",), "fusion.21": ("efhc.ys",),
           "fusion.22": ("efhc.init",), "fusion.23": ("efhc.event2",)}


def context(t: tr.Trace, att, iters: int = 7) -> Context:
    return Context(setup_s=1.0, window_s=att.window_s,
                   calls=[{"scan_iters": iters}], peak_bytes=None,
                   trace=tr.reduce(t), work={}, peaks={}, scopes=att)


def test_scoped_self_time_and_unscoped_remainder():
    att = sc.attribute(window_trace(), HLO_MAP)
    assert att.window_s == pytest.approx(10.0)
    # busy: [2, 5.5] u [7, 9] = 5.5 s; the while's own second is unscoped
    assert att.busy_s == pytest.approx(5.5)
    assert att.scope_s == pytest.approx({
        "efhc.event1": 1.0, "efhc.event3": 2.0, "efhc.event4": 1.0,
        "efhc.eval": 0.5, sc.UNSCOPED: 1.0})
    assert att.covered == pytest.approx(4.5 / 5.5)
    assert att.ops[:2] == [("fusion.1", "efhc.event3", pytest.approx(2.0)),
                           ("fusion.2", "efhc.event4", pytest.approx(1.0))]


def test_scopes_sum_to_step_device_ms():
    """graph_trigger + mix + local_step + eval + stats/ys/init + unscoped
    per scan iteration is the busy time per scan iteration, which is what
    ``step_device_ms`` reads."""
    t = window_trace()
    t.device_ops[0] += [op("fusion.20", 9.0, 9.1), op("fusion.21", 9.1, 9.15),
                        op("fusion.22", 2.0 - 1e-3, 2.0),
                        op("fusion.23", 9.15, 9.2)]
    att = sc.attribute(t, HLO_MAP)
    iters = 7
    ctx = context(t, att, iters)
    got = {m: READERS.reader(m)(ctx) for m in
           ("graph_trigger_device_ms", "mix_device_ms", "local_step_device_ms",
            "eval_device_ms", "step_device_ms")}
    rest = sum(att.scope_s[k] for k in ("efhc.stats", "efhc.ys", "efhc.init",
                                        sc.UNSCOPED))
    total = (got["graph_trigger_device_ms"] + got["mix_device_ms"]
             + got["local_step_device_ms"] + got["eval_device_ms"]
             + 1000.0 * rest / iters)
    assert abs(total - got["step_device_ms"]) < 1e-9
    assert got["graph_trigger_device_ms"] == pytest.approx(1000.0 * 1.05 / iters)


@pytest.mark.parametrize("metric,seconds", [
    ("graph_trigger_device_ms", 1.0), ("mix_device_ms", 2.0),
    ("local_step_device_ms", 1.0), ("eval_device_ms", 0.5),
])
def test_device_ms_readers(metric, seconds):
    t = window_trace()
    ctx = context(t, sc.attribute(t, HLO_MAP), iters=4)
    assert READERS.reader(metric)(ctx) == pytest.approx(1000.0 * seconds / 4)
    # an attribution that names none of the metric's scopes gives nothing
    assert READERS.reader(metric)(context(t, sc.attribute(t, {}), 4)) is None


@pytest.mark.parametrize("metric", [
    "graph_trigger_device_ms", "mix_device_ms", "local_step_device_ms",
    "eval_device_ms", "host_stage_share"])
def test_scope_readers_read_nothing_untraced(metric):
    ctx = Context(setup_s=1.0, window_s=10.0, calls=[{"scan_iters": 7}],
                  peak_bytes=None, trace=None, work={}, peaks={})
    assert READERS.reader(metric)(ctx) is None


def test_nested_prefix_keeps_the_event_totals():
    """A model's scopes nested in the step's: an op inside ``efhc.event4``
    counts toward Event 4 and toward its own layer; the step's scopes and
    ``unscoped`` still sum to busy time, and a model op outside every step
    scope stays unscoped there."""
    t = window_trace()
    t.device_ops[0].append(op("fusion.30", 9.2, 9.4))
    nested = {**HLO_MAP, "fusion.2": ("efhc.event4", "mla.attn"),
              "fusion.30": (None, "mla.experts")}
    flat = {k: v[:1] for k, v in nested.items()}
    got, want = sc.attribute(t, nested), sc.attribute(t, flat)
    assert {k: v for k, v in got.scope_s.items() if not k.startswith("mla.")} \
        == pytest.approx(want.scope_s)
    assert got.scope_s["mla.attn"] == pytest.approx(got.scope_s["efhc.event4"])
    assert got.scope_s["mla.experts"] == pytest.approx(0.2)
    step = sum(v for k, v in got.scope_s.items() if k.startswith("efhc."))
    assert step + got.scope_s[sc.UNSCOPED] == pytest.approx(got.busy_s)
    assert got.covered == pytest.approx(want.covered)


def test_host_stage_share_from_nested_program_spans():
    """A stage span counts once where spans nest in it or it repeats
    (the service's serial launches run ``sim.stage`` inside
    ``service.launch``); the benchmark's own spans count for nothing."""
    t = window_trace()
    att = sc.attribute(t, HLO_MAP)
    assert att.stage_s == pytest.approx(3.0)
    assert READERS.reader("host_stage_share")(context(t, att)) == pytest.approx(30.0)
    assert att.span_s == pytest.approx({"sim.stage": 3.0, "sim.launch": 0.3,
                                        "sim.fetch": 6.1})
    nested = tr.Trace(device_ops=[[]], host_spans=[
        span("bench.call", 0.0, 4.0), span("service.launch", 0.0, 4.0),
        span("sim.stage", 0.5, 1.5), span("service.stage", 1.0, 2.0),
        span("sim.stage", 3.5, 5.0)])
    assert sc.attribute(nested, {}).stage_s == pytest.approx(2.0)


def test_idle_gaps_are_named_by_program_spans():
    att = sc.attribute(window_trace(), HLO_MAP)
    # the last gap falls in the benchmark's own result span alone
    assert att.gaps == [("sim.stage", pytest.approx(2.0)),
                        ("sim.stage", pytest.approx(1.5)),
                        ("no program span", pytest.approx(1.0))]


def test_no_call_span_is_an_error():
    with pytest.raises(ValueError):
        sc.attribute(tr.Trace(device_ops=[[]], host_spans=[]), {})


@pytest.mark.parametrize("workload,spans", [
    ("fleet16k-ell", {"sim.stage", "sim.launch", "sim.fetch"}),
    ("paper-lenet-grid", {"service.stage", "service.launch", "service.fetch",
                          "service.report"}),
])
def test_tiny_cell_attributed_run(tiny, workload, spans):
    """A traced run of each cell through the harness, attributed: the
    program's spans name the window's host time, the run's own result is
    the harness's, and the staging share is among its per-layer metrics
    where the cell lists it."""
    import time

    import jax

    res = sc.run_attributed(tiny, workload, 2**31 + 7, 0.5,
                            devices=jax.devices()[:1], device_kind="",
                            t0=time.perf_counter(), log=lambda s: None)
    assert res["correct"] and "breakdown" in res
    got = res["scopes"]
    assert set(got["span_s"]) == spans
    assert got["scan_iters"] > 0
    listed = {m["name"] for m in tiny.metrics(workload, traced=True)}
    assert ("host_stage_share" in res["metrics"]) == ("host_stage_share" in listed)
    if "host_stage_share" in listed:
        assert 0.0 < res["metrics"]["host_stage_share"]["value"] < 100.0
