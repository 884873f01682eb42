"""The reduction from a profiler trace to busy time, idle share, the
largest device operations and the idle gaps named by host spans."""
from __future__ import annotations

import pytest

from bench import trace as tr


def ev(name, start, end):
    return tr.Event(name, start, end)


def small_trace():
    """Two chips over a 10 s window: chip 0 runs two overlapping ops and a
    third while the host stages; chip 1 is busy 1 s."""
    host = [ev("bench.call", 0.0, 6.0), ev("bench.stage", 0.5, 2.0),
            ev("bench.call", 6.0, 10.0), ev("bench.result", 8.0, 10.0)]
    chip0 = [ev("fusion.1", 2.0, 4.0), ev("fusion.2", 3.0, 5.0),
             ev("gather", 6.5, 7.5), ev("outside", 11.0, 12.0)]
    chip1 = [ev("fusion.1", 2.0, 3.0)]
    return tr.Trace(device_ops=[chip0, chip1], host_spans=host)


def test_union_merges_overlaps():
    assert tr.union([(3, 5), (2, 4), (6, 7), (7, 8)]) == [(2, 5), (6, 8)]


def test_busy_idle_and_gaps():
    red = tr.reduce(small_trace())
    assert red.window_s == pytest.approx(10.0)
    # chip 0: [2, 5] u [6.5, 7.5] = 4 s; chip 1: 1 s; mean 2.5 s
    assert red.busy_s == pytest.approx(2.5)
    assert red.idle_share == pytest.approx(0.75)
    # self time: the second in which fusion.2 runs inside fusion.1 counts
    # for fusion.2 alone
    assert dict(red.device_ops) == pytest.approx(
        {"fusion.1": 2.0, "fusion.2": 2.0, "gather": 1.0})
    # chip 0's gaps, longest first, named by the innermost span over each
    # gap's midpoint
    assert red.idle_gaps == [("bench.result", pytest.approx(2.5)),
                             ("bench.stage", pytest.approx(2.0)),
                             ("bench.call", pytest.approx(1.5))]


def test_short_op_names():
    assert tr._short("%fusion.12 = f32[8]{0} fusion(f32[8] %p), kind=kLoop") == "fusion.12"


def test_recorded_host_trace(tmp_path):
    """A trace recorded here: the host spans come back by name; the CPU
    has no device plane, so nothing is busy."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.call"):
            with jax.profiler.TraceAnnotation("bench.result"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path), chips=1)
    names = [e.name for e in t.host_spans]
    assert names.count("bench.call") == 2 and names.count("bench.result") == 2
    red = tr.reduce(t)
    assert red.busy_s == 0.0 and red.idle_share == 1.0
    assert red.window_s > 0


def test_no_call_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace(device_ops=[[]], host_spans=[ev("x", 0, 1)]))


def test_nested_operations_count_their_own_time():
    ops = [ev("while.1", 0.0, 10.0), ev("fusion.1", 1.0, 3.0),
           ev("while.2", 4.0, 9.0), ev("gather", 5.0, 8.0), ev("copy", 11.0, 12.0)]
    got = {n: own for n, _, _, own in tr.self_times(ops)}
    assert got == pytest.approx({"while.1": 3.0, "fusion.1": 2.0, "while.2": 2.0,
                                 "gather": 3.0, "copy": 1.0})
