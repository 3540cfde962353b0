"""Program spans (repro.core.spans, DESIGN.md Sec. 13's front door, the
bank's refresh): the ``trsm.*`` host spans a profiler trace holds and
how they nest, and the in-process record kept while the trace is
taken.  The trace is read with ``jax.profiler.ProfileData`` directly."""

import collections
import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import api
from repro.core import spans

pytestmark = pytest.mark.fast

N, C, PANEL = 32, 2, 4


def _factor(rng):
    return (np.tril(rng.standard_normal((N, N)))
            + N * np.eye(N)).astype(np.float32)


def _host_spans(trace_dir) -> list:
    """[(name, start_ns, end_ns, thread line)] of the trace's trsm.*
    host events."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.end_ns, i) for e in line.events
                    if e.name.startswith(spans.PREFIX)]
    return out


def _inside(child, parents) -> bool:
    _, s, e, line = child
    return any(p[3] == line and p[1] <= s and e <= p[2] for p in parents)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A small served bank under a profiler trace: a few submits
    through the background drain loop, then one replace.  Returns the
    trace's trsm.* host events and the record kept meanwhile."""
    rng = np.random.default_rng(0)
    grid = api.make_trsm_mesh(1, 1)
    bank = api.FactorBank(grid, N, n0=8, capacity=C, dtype=np.float32)
    for _ in range(C):
        bank.admit(_factor(rng))
    srv = api.AsyncSolveServer(api.Solver.from_bank(bank), PANEL,
                               max_inflight=1).warmup()
    bank.replace(0, _factor(rng))               # the updater compiled
    jax.block_until_ready(bank.stacks())
    tdir = str(tmp_path_factory.mktemp("trace"))
    spans.clear()
    jax.profiler.start_trace(tdir)
    with srv:
        futures = [srv.submit(rng.standard_normal((N, 1))
                              .astype(np.float32), factor=i % C)
                   for i in range(6)]
        for f in futures:
            f.result(timeout=60)
    bank.replace(1, _factor(rng))
    jax.block_until_ready(bank.stacks())
    jax.profiler.stop_trace()
    kept = spans.recorded()
    spans.clear()
    return _host_spans(tdir), kept


def test_front_door_spans_nest(traced):
    events, _ = traced
    by = collections.defaultdict(list)
    for ev in events:
        by[ev[0]].append(ev)
    assert len(by["trsm.submit"]) == 6
    for child in ("trsm.submit.upload", "trsm.submit.enqueue"):
        assert len(by[child]) == 6
        assert all(_inside(c, by["trsm.submit"]) for c in by[child])
    for child in ("trsm.pack", "trsm.dispatch"):
        assert by[child]
        assert all(_inside(c, by["trsm.step"]) for c in by[child])
    # the waves the loop finalizes itself are waited on inside a step
    # (stop's final flush is the only other caller)
    for child in ("trsm.device_wait", "trsm.resolve"):
        assert any(_inside(c, by["trsm.step"]) for c in by[child])


def test_replace_span(traced):
    events, _ = traced
    assert sum(ev[0] == "trsm.replace" for ev in events) == 1


def test_record_holds_what_the_trace_holds(traced):
    events, kept = traced
    in_trace = collections.Counter(ev[0] for ev in events)
    in_record = collections.Counter(r[0] for r in kept
                                    if r[0] != "trsm.queue")
    assert in_record == in_trace
    # one queue wait per request dispatched, none negative
    waits = [e - s for n, s, e, _ in kept if n == "trsm.queue"]
    assert len(waits) == 6 and min(waits) >= 0
    assert all(s <= e and cpu >= 0 for _, s, e, cpu in kept)


def test_cpu_time_leaves_out_sleep(monkeypatch):
    """A span keeps the thread's CPU time beside its length: a thread
    that sleeps inside it (as on a wait for the device) does no host
    work there."""
    monkeypatch.setattr(spans, "_active", lambda: True)
    spans.clear()
    try:
        with spans.span("dispatch"):
            time.sleep(0.05)
        (name, s, e, cpu), = spans.recorded()
    finally:
        spans.clear()
    assert name == "trsm.dispatch"
    assert e - s >= 50_000_000 and 0 <= cpu < 10_000_000


def test_nothing_kept_without_a_profiler():
    spans.clear()
    with spans.span("submit"):
        pass
    spans.record("queue", 1e-3)
    assert spans.recorded() == []


def test_wave_spans_nest_in_dispatch(traced):
    """The drain thread's dispatch splits into the wave's assembly, the
    solve program's launch and the result slices, once each a wave."""
    events, _ = traced
    by = collections.defaultdict(list)
    for ev in events:
        by[ev[0]].append(ev)
    for child in ("trsm.wave.assemble", "trsm.wave.launch",
                  "trsm.wave.slice"):
        assert len(by[child]) == len(by["trsm.dispatch"]), child
        assert all(_inside(c, by["trsm.dispatch"]) for c in by[child])


def test_admit_spans_nest(tmp_path):
    """Admission under a trace, natural and cyclic: ``trsm.admit``
    holds the ingestion (one gather, or one cast per resident dtype)
    and phase 1, and the record keeps what the trace holds."""
    rng = np.random.default_rng(1)
    grid = api.make_trsm_mesh(1, 1)
    bank = api.FactorBank(grid, N, n0=8, precision="bf16_refine")
    L = _factor(rng)
    bank.admit(L)                               # programs compiled
    bank.admit_cyclic(L)        # on (1, 1) cyclic storage is natural
    spans.clear()
    jax.profiler.start_trace(str(tmp_path))
    bank.admit(L)
    bank.admit_cyclic(L)
    jax.profiler.stop_trace()
    kept = spans.recorded()
    spans.clear()
    events = [ev for ev in _host_spans(str(tmp_path))
              if ev[0].startswith("trsm.admit")]
    by = collections.defaultdict(list)
    for ev in events:
        by[ev[0]].append(ev)
    assert len(by["trsm.admit"]) == 2
    assert len(by["trsm.admit.phase1"]) == 2
    assert len(by["trsm.admit.ingest"]) == 3    # a gather; two casts
    for child in ("trsm.admit.ingest", "trsm.admit.phase1"):
        assert all(_inside(c, by["trsm.admit"]) for c in by[child])
    assert collections.Counter(r[0] for r in kept) \
        == collections.Counter(ev[0] for ev in events)


def test_collective_counters_zero_on_one_device():
    """``collectives_per_solve`` and ``collective_words_per_col`` are
    None before the first solve and 0 on a (1, 1) mesh, in the
    Solver's stats and the server's (positive on (2, 1): selfcheck
    ``cyclic_serve``)."""
    rng = np.random.default_rng(2)
    solver = api.Solver.from_factor(_factor(rng), api.make_trsm_mesh(1, 1),
                                    n0=8, precision="bf16_refine")
    assert solver.stats()["collectives_per_solve"] is None
    srv = api.AsyncSolveServer(solver, PANEL).warmup()
    with srv:
        srv.submit(rng.standard_normal(N).astype(np.float32)).result(
            timeout=60)
    for stats in (solver.stats(), srv.stats()):
        assert stats["collectives_per_solve"] == 0
        assert stats["collective_words_per_col"] == 0


@pytest.mark.parametrize("precision,share", [("bf16_refine", 17 / 32),
                                             ("fp32", None)])
def test_residual_tile_share_on_one_device(precision, share):
    """``residual_tile_share`` is None before the first solve; on a
    (1, 1) mesh at order 8192 the bf16_refine residual is sixteen row
    blocks, (T+1)/(2T) = 17/32 of the dense GEMM's multiply-adds, and
    the program holds sixteen f32 products a pass; ``fp32`` does not
    refine: None (1.0 on (2, 1): selfcheck ``residual_dense``)."""
    n, k = 8192, 4
    L = (np.tril(np.random.default_rng(3).uniform(-0.5, 0.5, (n, n)), -1)
         + n * np.eye(n)).astype(np.float32)
    solver = api.Solver.from_factor(L, api.make_trsm_mesh(1, 1),
                                    precision=precision)
    assert solver.stats()["residual_tile_share"] is None
    srv = api.AsyncSolveServer(solver, k).warmup()
    with srv:
        srv.submit(np.ones(n, np.float32)).result(timeout=120)
    for stats in (solver.stats(), srv.stats()):
        assert stats["residual_tile_share"] == share
    if share is not None:
        prog = solver.program_for(k)
        text = prog.solve.lower(solver.bank.stacks(), solver.place_rhs(
            np.zeros((n, k), np.float32))).as_text()
        highest = [ln for ln in text.splitlines() if "dot_general" in ln
                   and "precision = [HIGHEST, HIGHEST]" in ln]
        assert len(highest) == 16 * prog.policy.refine_steps
