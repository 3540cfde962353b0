"""The trace reduction against a small recorded trace
(data/small_trace.json) whose values are counted by hand here."""

import json
import pathlib
import types

import pytest

from bench import trace
from bench.metrics import (collective_exposed_pct, device_idle_pct,
                           refresh_device_ms, solve_roofline)

DATA = pathlib.Path(__file__).parent / "data" / "small_trace.json"
PEAK = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


@pytest.fixture
def ctx():
    tr = json.loads(DATA.read_text())
    return types.SimpleNamespace(
        trace=tr, window=trace.window(tr),
        programs={"solve": "jit_program", "update": "jit_update"},
        solve_work={"W": 2e6, "Q": 1e5}, peak=PEAK, chips=2,
        counters={}, admit_s=None, notes=[])


def test_window_is_the_window_span(ctx):
    assert ctx.window == (1000, 11000)


def test_device_idle_pct(ctx):
    # TPU:0 ops cut to [1000, 11000]: [1000,5000] (three ops merged),
    # [7500,9000], [9500,10000] -> 6000 ns busy, 40% idle.  TPU:1:
    # [1000,6000], [8000,10500] -> 7500 busy, 25% idle.  Worst: 40%.
    assert device_idle_pct.read(ctx) == pytest.approx(40.0)
    assert trace.busy(ctx.trace, ctx.window) == {
        "/device:TPU:0": 6000.0, "/device:TPU:1": 7500.0}


def test_collective_exposed_pct(ctx):
    # TPU:0: all-reduce [3500,5000] minus convolution to 4000 -> 1000;
    # all-gather-start [9500,10000] alone -> 500; 1500 of 10000 = 15%.
    # TPU:1: all-reduce [3000,6000] alone -> 3000 = 30%.  Worst: 30%.
    assert collective_exposed_pct.read(ctx) == pytest.approx(30.0)


def test_solve_roofline(ctx):
    # solve executions inside the window: TPU:0 [1500,5000] (the one at
    # 9500 ends after the window) = 3500 ns; TPU:1 [1000,6000] = 5000 ns.
    # Slowest device 5e-6 s.  Least time: max(2e6 / 2e12, 1e5 / 2e11)
    # = max(1e-6, 5e-7) = 1e-6 s, compute-bound -> 20%.
    assert solve_roofline.read(ctx) == pytest.approx(20.0)
    assert "compute-bound" in ctx.notes[0]


def test_refresh_device_ms(ctx):
    # update executions: 1500 ns on TPU:0, 2500 ns on TPU:1 -> slowest
    # median 2500 ns = 0.0025 ms
    assert refresh_device_ms.read(ctx) == pytest.approx(0.0025)


def test_no_trace_reads_nothing(ctx):
    ctx.trace = None
    for m in (device_idle_pct, collective_exposed_pct, refresh_device_ms,
              solve_roofline):
        assert m.read(ctx) is None


def test_idle_gaps_name_the_host_activity(ctx):
    # TPU:0 gaps in the window: [5000,7500] (middle 6250: refresh),
    # [10000,11000] (middle 10500: wait), [9000,9500] (9250: wait)
    assert trace.idle_gaps(ctx.trace, ctx.window) == [
        ["bench.refresh", 2.5e-6], ["bench.wait", 1e-6],
        ["bench.wait", 5e-7]]


def test_top_ops(ctx):
    top = dict(trace.top_ops(ctx.trace, ctx.window))
    # fusion.1: TPU:0 1000 + 1500, TPU:1 2000 + 2500 -> 7000 / 2 devices
    assert top["fusion.1"] == pytest.approx(3.5e-6)


def test_subtract_and_union():
    assert trace.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 6)]) == [
        (0, 2), (3, 5), (6, 10)]
    assert trace.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_load_reads_host_spans_of_a_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    win = trace.window(tr)
    assert win is not None and win[1] > win[0]
