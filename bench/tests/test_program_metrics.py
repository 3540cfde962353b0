"""The readers of the program's own spans (bench/program.py and the
metrics over it) against a small recorded set of spans
(data/small_program_trace.json) whose values are counted by hand here."""

import json
import pathlib
import sys
import types

import pytest

from bench import program, trace
from bench.metrics import (drain_host_pct, queue_wait_p95_ms,
                           refresh_host_ms, submit_ms)

DATA = pathlib.Path(__file__).parent / "data" / "small_program_trace.json"
READERS = (submit_ms, drain_host_pct, queue_wait_p95_ms, refresh_host_ms)


@pytest.fixture
def ctx(monkeypatch):
    data = json.loads(DATA.read_text())
    monkeypatch.setattr(program, "recorded", lambda: data["program"])
    return types.SimpleNamespace(
        trace=data["trace"], window=trace.window(data["trace"]),
        programs={}, solve_work={}, peak=None, chips=1, counters={},
        admit_s=None, notes=[])


def test_submit_ms(ctx):
    # trsm.submit lasts 400, 600 and 1400 ns: mean 800 ns (median 600)
    assert submit_ms.read(ctx) == pytest.approx(8e-4)


def test_drain_host_pct(ctx):
    # the first bench.submit (trace clock 1000) opens with the first
    # trsm.submit (500000): the spans move by -499000.  In the window
    # [1000, 11000] the CPU time of pack [1500,1600] 80, dispatch
    # [1600,2600] 300, resolve [2600,3000] 200 and dispatch [4000,5000]
    # 500 counts whole; dispatch [10500,12000] lies a third inside, so
    # 600 / 3 = 200 of its 600; the pack at [0,100] lies before the
    # window, and neither the device_wait nor the launch (a part of a
    # dispatch) counts.  1280 of 10000 ns = 12.8%.
    assert drain_host_pct.read(ctx) == pytest.approx(12.8)


def test_drain_host_pct_needs_the_trace_clock(ctx):
    ctx.trace = None
    assert drain_host_pct.read(ctx) is None


def test_queue_wait_p95_ms(ctx):
    # waits of 1000..5000 ns: the 95th percentile interpolates between
    # 4000 and 5000 at 0.8 -> 4800 ns
    assert queue_wait_p95_ms.read(ctx) == pytest.approx(4.8e-3)


def test_refresh_host_ms(ctx):
    # trsm.replace lasts 300, 100, 500 and 200 ns: median 250 ns
    assert refresh_host_ms.read(ctx) == pytest.approx(2.5e-4)


def test_no_spans_read_nothing(ctx, monkeypatch):
    monkeypatch.setattr(program, "recorded", lambda: [])
    for m in READERS:
        assert m.read(ctx) is None


def test_a_program_without_spans_reads_nothing(monkeypatch):
    # the module cannot be imported: the program keeps no spans
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert program.recorded() == []


def test_recorded_reads_the_program(monkeypatch):
    from repro.core import spans
    monkeypatch.setattr(spans, "_active", lambda: True)
    spans.clear()
    try:
        with spans.span("submit"):
            pass
        spans.record("queue", 2e-3)
        got = program.recorded()
    finally:
        spans.clear()
    assert [r[0] for r in got] == ["trsm.submit", "trsm.queue"]
    assert got[0][3] >= 0
    assert got[1][2] - got[1][1] == 2_000_000 and got[1][3] == 0


def test_idle_gaps_named_by_program_span(ctx):
    # moved as above, the first launch sits at [1800,2400] inside the
    # dispatch [1600,2600], and the last submit at [6000,7400].  TPU:0
    # gaps in [1000,11000]: [1600,3000] (middle 2300, innermost in the
    # launch) and [5000,5900] (middle 5450, inside no program span).
    ctx.trace["devices"] = {"/device:TPU:0": {
        "ops": [["fusion.1", 1000, 1600], ["fusion.2", 3000, 5000],
                ["fusion.3", 5900, 11000]],
        "modules": []}}
    drain_host_pct.read(ctx)
    assert ctx.notes == ["idle gaps by program span: trsm.wave.launch "
                         "0.001400 ms, untraced 0.000900 ms"]
    ctx.notes.clear()
    ctx.trace["devices"] = {}                 # a rehearsal: no device
    drain_host_pct.read(ctx)
    assert ctx.notes == []
