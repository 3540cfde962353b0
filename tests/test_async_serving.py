"""Open-loop async serving (repro.core.serving / DESIGN.md Sec. 13):
queue bounds + typed shedding, FIFO-per-tenant ordering and future
resolution order, weighted fair packing, evict-under-flight stranding
through the future (plain AND fleet), the zero-retrace/zero-transfer
steady state, the single-column path (host ingest, waves whose programs
do not depend on the fill), and a producer-thread stress with capacity
churn.

Everything except the lifecycle/stress tests runs with NO background
thread and NO wall-clock: the server gets the ``fake_clock`` fixture
and a ``DrainDriver`` (tests/conftest.py) steps waves by hand.
"""

import threading

import jax
import numpy as np
import pytest

from repro import api
from repro.core import session
from repro.core.serving import FairQueue, _Request

pytestmark = pytest.mark.fast


@pytest.fixture(scope="module")
def grid():
    return api.make_trsm_mesh(1, 1)


def _factors(M, n=32, seed=0):
    rng = np.random.default_rng(seed)
    Ls = np.stack([np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
                   for _ in range(M)])
    return Ls.astype(np.float32), rng


def _server(grid, *, M=2, n=32, capacity=None, panel_k=4, **kw):
    Ls, rng = _factors(M, n)
    bank = api.FactorBank(grid, n, n0=8, capacity=capacity,
                          dtype=np.float32)
    if capacity is None:
        bank.admit_stack(Ls)
    else:
        for L in Ls:
            bank.admit(L)
    solver = api.Solver.from_bank(bank)
    return (api.AsyncSolveServer(solver, panel_k, **kw).warmup(),
            Ls, bank, rng)


def _rel(L, X, b):
    X = np.asarray(X, np.float64)
    return (np.linalg.norm(L.astype(np.float64) @ X - np.asarray(b))
            / max(np.linalg.norm(b), 1e-30))


# ---------------------- futures + wave correctness ----------------------

def test_futures_resolve_correct_solutions(grid, fake_clock,
                                           drain_driver):
    srv, Ls, _, rng = _server(grid, clock=fake_clock)
    drv = drain_driver(srv)
    reqs = [(i % 2, rng.standard_normal((32, 1 + i % 3))
             .astype(np.float32)) for i in range(7)]
    futs = [srv.submit(b, factor=f) for f, b in reqs]
    assert srv.pending() == 7 and not any(f.done() for f in futs)
    drv.run_until_idle(advance=0.25)
    for (f, b), fut in zip(reqs, futs):
        assert fut.done() and fut.exception() is None
        assert _rel(Ls[f], fut.result(), b) < 1e-4
        assert fut.result().shape == b.shape
        # completion stamps come from the injected clock
        assert fut.latency() is not None and fut.latency() > 0
    st = srv.stats()
    assert st["served"] == 7 and st["shed"] == 0
    assert st["p99_ms"] >= st["p50_ms"] > 0


def test_vector_rhs_served_as_column(grid, fake_clock, drain_driver):
    srv, Ls, _, rng = _server(grid, clock=fake_clock)
    b = rng.standard_normal(32).astype(np.float32)
    fut = srv.submit(b)
    drain_driver(srv).run_until_idle()
    assert fut.result().shape == (32, 1)
    assert _rel(Ls[0], fut.result()[:, 0], b) < 1e-4


def test_future_timeout_raises_not_hangs(grid, fake_clock):
    srv, _, _, rng = _server(grid, clock=fake_clock)
    fut = srv.submit(rng.standard_normal((32, 1)).astype(np.float32))
    with pytest.raises(TimeoutError, match="drain loop"):
        fut.result(timeout=0.01)    # nobody is stepping the server
    with pytest.raises(TimeoutError):
        fut.exception(timeout=0.01)


# ------------------- admission control / queue bounds -------------------

def test_queue_bound_sheds_with_typed_overloaded(grid, fake_clock,
                                                 drain_driver):
    srv, Ls, _, rng = _server(grid, queue_depth=3, clock=fake_clock)
    bs = [rng.standard_normal((32, 1)).astype(np.float32)
          for _ in range(3)]
    futs = [srv.submit(b, factor=1) for b in bs]
    with pytest.raises(api.Overloaded, match="shed"):
        srv.submit(bs[0], factor=1)
    # per-slot bound: the OTHER slot's queue still admits
    other = srv.submit(bs[0], factor=0)
    assert srv.stats()["shed"] == 1 and srv.pending() == 4
    # shedding never poisons the queue: everything admitted serves
    drain_driver(srv).run_until_idle()
    for b, f in zip(bs, futs):
        assert _rel(Ls[1], f.result(), b) < 1e-4
    assert other.done() and srv.stats()["served"] == 4


def test_submit_validation_errors(grid, fake_clock):
    srv, _, bank, rng = _server(grid, M=2, capacity=4,
                                clock=fake_clock)
    b = rng.standard_normal((32, 1)).astype(np.float32)
    with pytest.raises(ValueError, match="unknown factor"):
        srv.submit(b, factor=7)
    with pytest.raises(ValueError, match="inactive slot"):
        srv.submit(b, factor=3)
    with pytest.raises(ValueError, match="wider than panel"):
        srv.submit(rng.standard_normal((32, 9)).astype(np.float32))
    with pytest.raises(ValueError, match=r"must be \(32, j\)"):
        srv.submit(rng.standard_normal((16, 1)).astype(np.float32))
    with pytest.raises(ValueError, match="needs a fleet"):
        srv.submit(b, tag="adapter")
    # validation rejects are NOT sheds, and nothing was enqueued
    assert srv.stats()["shed"] == 0 and srv.pending() == 0


# ----------------------- ordering and fairness -----------------------

def _waves_of(srv, futs, drv, max_waves=50):
    """Step until idle, recording which futures complete on each
    step — the observable wave/resolution order."""
    waves = []
    for _ in range(max_waves):
        before = [f.done() for f in futs]
        drv.step(advance=0.1)
        newly = [i for i, (was, f) in enumerate(zip(before, futs))
                 if not was and f.done()]
        if newly:
            waves.append(newly)
        if not srv.pending() and not srv._inflight:
            break
    assert all(f.done() for f in futs)
    return waves


def test_fifo_per_tenant_and_resolution_order(grid, fake_clock,
                                              drain_driver):
    """Per tenant, futures resolve in submit order, and completion
    timestamps are nondecreasing across waves."""
    srv, _, _, rng = _server(grid, M=1, panel_k=2, max_inflight=1,
                             clock=fake_clock)
    futs = []
    for i in range(6):
        t = "alice" if i % 2 == 0 else "bob"
        futs.append(srv.submit(
            rng.standard_normal((32, 1)).astype(np.float32), tenant=t))
    waves = _waves_of(srv, futs, drain_driver(srv))
    assert len(waves) == 3 and all(len(w) == 2 for w in waves)
    flat = [i for w in waves for i in w]
    for tenant in ("alice", "bob"):
        order = [i for i in flat if futs[i].tenant == tenant]
        assert order == sorted(order)          # FIFO per tenant
    stamps = [futs[w[0]].completed for w in waves]
    assert stamps == sorted(stamps)


def test_weighted_fairness_within_one_wave(grid, fake_clock,
                                           drain_driver):
    """Backlogged 3:1 tenants split an 8-wide panel 6:2 in the first
    wave (unit-width requests => exact weight proportionality)."""
    srv, _, _, rng = _server(grid, M=1, panel_k=8, max_inflight=1,
                             queue_depth=32,
                             weights={"a": 3.0, "b": 1.0},
                             clock=fake_clock)
    futs = []
    for i in range(8):                         # interleaved arrivals
        for t in ("a", "b"):
            futs.append(srv.submit(
                rng.standard_normal((32, 1)).astype(np.float32),
                tenant=t))
    waves = _waves_of(srv, futs, drain_driver(srv))
    first = [futs[i].tenant for i in waves[0]]
    assert len(first) == 8
    assert first.count("a") == 6 and first.count("b") == 2
    # weights shape WHO shares a wave, never whether someone is served
    assert all(f.done() and f.exception() is None for f in futs)


def test_unweighted_tenants_share_equally(grid, fake_clock,
                                          drain_driver):
    srv, _, _, rng = _server(grid, M=1, panel_k=4, max_inflight=1,
                             queue_depth=32, clock=fake_clock)
    futs = [srv.submit(rng.standard_normal((32, 1)).astype(np.float32),
                       tenant=t)
            for _ in range(4) for t in ("a", "b")]
    waves = _waves_of(srv, futs, drain_driver(srv))
    for w in waves:
        tenants = [futs[i].tenant for i in w]
        assert tenants.count("a") == 2 and tenants.count("b") == 2


def test_max_inflight_pipelines_waves(grid, fake_clock, drain_driver):
    """With the default pipeline depth, one wave stays un-finalized
    while the next is packed (async dispatch overlap); flush()
    resolves the tail."""
    srv, _, _, rng = _server(grid, M=1, panel_k=1, max_inflight=2,
                             clock=fake_clock)
    futs = [srv.submit(rng.standard_normal((32, 1)).astype(np.float32))
            for _ in range(3)]
    drv = drain_driver(srv)
    drv.step()
    assert len(srv._inflight) == 1 and not futs[0].done()
    drv.step()                      # dispatch #2 finalizes #1
    assert futs[0].done() and not futs[1].done()
    drv.step()
    assert futs[1].done() and not futs[2].done()
    srv.flush()
    assert futs[2].done() and len(srv._inflight) == 0


# -------------------- evict-under-flight: stranding --------------------

def test_stranded_future_on_evict_then_readmit_plain(grid, fake_clock,
                                                     drain_driver):
    """The generation counter catches slot TURNOVER, not just death:
    evict + re-admit leaves the slot live, but the queued request
    fails through its future with the typed error — no hang, no solve
    against the new occupant."""
    srv, Ls, bank, rng = _server(grid, M=2, capacity=2,
                                 clock=fake_clock)
    Lnew, _ = _factors(1, seed=99)
    b = rng.standard_normal((32, 1)).astype(np.float32)
    stale = srv.submit(b, factor=1)
    bank.evict(1)
    assert bank.admit(Lnew[0]) == 1 and bank.is_live(1)
    fresh = srv.submit(b, factor=1)       # new generation: stays valid
    drv = drain_driver(srv)
    drv.run_until_idle()
    err = stale.exception(timeout=0)
    assert isinstance(err, api.StrandedRequestError)
    assert isinstance(err, ValueError)    # old except-clauses keep working
    assert "evicted after submission" in str(err)
    with pytest.raises(api.StrandedRequestError):
        stale.result(timeout=0)
    assert _rel(Lnew[0], fresh.result(timeout=0), b) < 1e-4
    st = srv.stats()
    assert st["stranded"] == 1 and st["served"] >= 1


def test_dead_slot_strands_whole_queue_plain(grid, fake_clock,
                                             drain_driver):
    srv, _, bank, rng = _server(grid, M=2, capacity=2,
                                clock=fake_clock)
    futs = [srv.submit(rng.standard_normal((32, 1)).astype(np.float32),
                       factor=0) for _ in range(3)]
    bank.evict(0)
    drain_driver(srv).run_until_idle()
    for f in futs:
        assert isinstance(f.exception(timeout=0),
                          api.StrandedRequestError)
    assert srv.stats()["stranded"] == 3


def test_stranded_future_on_fleet_cross_tenant_reclaim(grid,
                                                       fake_clock,
                                                       drain_driver):
    """Fleet mode records the FleetHandle generation at submit; a
    cross-tenant LRU reclaim of the slot strands exactly the displaced
    tenant's queued requests while the reclaimer's serve fine."""
    plan = api.plan_fleet({64: 1}, grid=grid)
    assert plan.buckets[0].capacity == 1      # full => admit reclaims
    fleet = api.SolverFleet(grid, plan)
    Ls, rng = _factors(2, n=64, seed=3)
    fleet.admit(Ls[0], tenant="alice")
    srv = api.AsyncSolveServer(fleet, panel_k=4,
                               clock=fake_clock).warmup()
    b = rng.standard_normal((64, 1)).astype(np.float32)
    doomed = srv.submit(b, tenant="alice")
    fleet.admit(Ls[1], tenant="bob")          # reclaims alice's slot
    fresh = srv.submit(b, tenant="bob")
    drain_driver(srv).run_until_idle()
    assert isinstance(doomed.exception(timeout=0),
                      api.StrandedRequestError)
    assert _rel(Ls[1], fresh.result(timeout=0), b) < 1e-4
    # and alice's route is gone at ADMISSION now, not at drain
    with pytest.raises(KeyError, match="re-admit"):
        srv.submit(b, tenant="alice")


def test_fleet_async_mixed_orders_slice_back(grid, fake_clock,
                                             drain_driver):
    """Mixed-order tenants share a bucket; each solution comes back at
    its TRUE order (padded rows sliced off)."""
    plan = api.plan_fleet({48: 1, 64: 1}, grid=grid)
    fleet = api.SolverFleet(grid, plan)
    rng = np.random.default_rng(4)
    Ls = {}
    for t, order in (("alice", 48), ("bob", 64)):
        L = (np.tril(rng.standard_normal((order, order)))
             + order * np.eye(order)).astype(np.float32)
        Ls[t] = L
        fleet.admit(L, tenant=t)
    srv = api.AsyncSolveServer(fleet, panel_k=4,
                               clock=fake_clock).warmup()
    futs = {t: srv.submit(
        rng.standard_normal((L.shape[0], 2)).astype(np.float32),
        tenant=t) for t, L in Ls.items()}
    drain_driver(srv).run_until_idle()
    for t, f in futs.items():
        X = f.result(timeout=0)
        assert X.shape == (Ls[t].shape[0], 2)
        assert f.exception() is None


def test_fleet_async_host_vector_padded_on_host_and_sliced_back(
        grid, fake_clock, drain_driver):
    """A host vector shorter than its bucket is cast and zero-padded
    to the bucket order on the host, uploaded once, and its solution
    comes back at its true order."""
    # a dispatch priced high enough that both orders share one bucket
    plan = api.plan_fleet({48: 1, 64: 1}, grid=grid, dispatch_s=1.0)
    assert [b.n for b in plan.buckets] == [64]
    fleet = api.SolverFleet(grid, plan)
    rng = np.random.default_rng(5)
    Ls = {}
    for t, order in (("alice", 48), ("bob", 64)):
        Ls[t] = (np.tril(rng.standard_normal((order, order)))
                 + order * np.eye(order)).astype(np.float32)
        fleet.admit(Ls[t], tenant=t)
    srv = api.AsyncSolveServer(fleet, panel_k=4,
                               clock=fake_clock).warmup()
    b = rng.standard_normal(48)               # float64, as callers hold
    fut = srv.submit(b, tenant="alice")
    (fq,) = [q for q in srv._queues.values() if len(q)]
    staged = fq._reqs[0].b
    assert isinstance(staged, jax.Array) and staged.shape == (64, 1)
    assert staged.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(staged)[48:], 0)
    np.testing.assert_array_equal(np.asarray(staged)[:48, 0],
                                  b.astype(np.float32))
    drain_driver(srv).run_until_idle()
    X = fut.result(timeout=0)
    assert X.shape == (48, 1)
    assert _rel(Ls["alice"], X[:, 0], b) < 1e-4
    assert srv.stats()["host_ingested"] == 1


# ------------------------- the steady state -------------------------

def _zero_retrace_zero_transfer(grid, fake_clock, drain_driver, cols):
    srv, Ls, _, rng = _server(grid, M=2, panel_k=4, max_inflight=1,
                              clock=fake_clock)
    key = srv.solver.program_for(srv.panel_k).key
    import jax.numpy as jnp
    bs = [jnp.asarray(rng.standard_normal((32, cols))
                      .astype(np.float32)) for _ in range(8)]
    jax.block_until_ready(bs)
    drv = drain_driver(srv)
    srv.submit(bs[0], factor=0)               # priming wave
    drv.run_until_idle()
    before = session.TRACE_COUNTS[key]
    st0 = srv.stats()
    with jax.transfer_guard("disallow"):
        futs = [srv.submit(b, factor=i % 2)
                for i, b in enumerate(bs)]
        drv.run_until_idle()
    assert session.TRACE_COUNTS[key] == before   # zero retraces
    for i, (b, f) in enumerate(zip(bs, futs)):
        assert _rel(Ls[i % 2], f.result(timeout=0), np.asarray(b)) \
            < 1e-4
    st = srv.stats()
    return st["waves"] - st0["waves"], st["unit_waves"] - st0["unit_waves"]


def test_async_steady_state_zero_retrace_zero_transfer(grid,
                                                       fake_clock,
                                                       drain_driver):
    """After warmup + one priming wave, waves pack and dispatch with
    ZERO retraces and ZERO host->device transfers — submits of
    device-resident RHS included (the acceptance invariant the open
    Poisson bench leans on)."""
    waves, unit = _zero_retrace_zero_transfer(grid, fake_clock,
                                              drain_driver, cols=2)
    assert waves >= 1 and unit == 0


def test_async_steady_state_zero_retrace_zero_transfer_unit_width(
        grid, fake_clock, drain_driver):
    """The same invariant for device (n, 1) submits, which take the
    unit-wave programs (assemble, split) instead of the per-request
    slices."""
    waves, unit = _zero_retrace_zero_transfer(grid, fake_clock,
                                              drain_driver, cols=1)
    assert waves >= 1 and unit == waves


# ----------------------- the unit-column path -----------------------

def _counting_compiles():
    seen = []

    def listen(event, *_a, **_k):
        if "backend_compile" in event:
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    return seen, lambda: \
        jax.monitoring.unregister_event_duration_listener(listen)


@pytest.mark.parametrize("M", [1, 2])
def test_unit_waves_compile_nothing_at_any_fill(grid, fake_clock,
                                                drain_driver, M):
    """After warmup and one priming wave, host-vector waves of every
    fill 1..panel_k compile nothing, and every answer equals, bit for
    bit, the same column sent as a device (n, 1) array through the
    general path (a wave mixed with a width-2 request)."""
    import jax.numpy as jnp
    pk = 8
    srv, Ls, _, rng = _server(grid, M=M, panel_k=pk, clock=fake_clock)
    drv = drain_driver(srv)
    srv.submit(rng.standard_normal(32).astype(np.float32))
    drv.run_until_idle()                       # priming wave
    sent, futs = [], []
    seen, stop = _counting_compiles()
    try:
        for fill in range(1, pk + 1):
            for c in range(fill * M):
                b = rng.standard_normal(32).astype(np.float32)
                sent.append((c % M, b))
                futs.append(srv.submit(b, factor=c % M))
            assert drv.step() == fill * M      # one wave, this fill
            drv.run_until_idle()
    finally:
        stop()
    assert seen == []
    pair = jnp.asarray(rng.standard_normal((32, 2)).astype(np.float32))
    for (f, b), fut in zip(sent, futs):
        ref = srv.submit(jnp.asarray(b[:, None]), factor=f)
        srv.submit(pair, factor=f)
        drv.run_until_idle()
        np.testing.assert_array_equal(np.asarray(fut.result(timeout=0)),
                                      np.asarray(ref.result(timeout=0)))
        assert _rel(Ls[f], fut.result(timeout=0)[:, 0], b) < 1e-4


@pytest.mark.parametrize("host", [True, False])
def test_stats_count_host_ingest_and_unit_waves(grid, fake_clock,
                                                drain_driver, host):
    """Host single columns: every request host-ingested, every wave a
    unit wave.  Device width-2 blocks: neither."""
    import jax.numpy as jnp
    srv, _, _, rng = _server(grid, clock=fake_clock)
    for i in range(10):
        if host:
            b = rng.standard_normal(32).astype(np.float32)
        else:
            b = jnp.asarray(rng.standard_normal((32, 2))
                            .astype(np.float32))
        srv.submit(b, factor=i % 2)
    drain_driver(srv).run_until_idle()
    st = srv.stats()
    assert st["served"] == 10 and st["waves"] > 1
    if host:
        assert st["host_ingested"] == 10 and st["unit_waves"] == \
            st["waves"]
    else:
        assert st["host_ingested"] == 0 and st["unit_waves"] == 0


# ----------------------- lifecycle + the thread -----------------------

def test_context_manager_runs_real_drain_loop(grid):
    srv, Ls, _, rng = _server(grid)
    spawned = []
    real_factory = threading.Thread

    def factory(**kw):                        # injectable executor
        t = real_factory(**kw)
        spawned.append(t)
        return t

    srv._thread_factory = factory
    bs = [rng.standard_normal((32, 1)).astype(np.float32)
          for _ in range(5)]
    with srv:
        futs = [srv.submit(b, factor=i % 2) for i, b in enumerate(bs)]
        outs = [f.result(timeout=60) for f in futs]
    assert len(spawned) == 1 and not spawned[0].is_alive()
    for i, (b, X) in enumerate(zip(bs, outs)):
        assert _rel(Ls[i % 2], X, b) < 1e-4
    with pytest.raises(RuntimeError, match="already running"):
        with srv:
            srv.start()


def test_stop_drains_queued_work(grid):
    """stop(drain=True) serves everything still queued, so no future
    is ever left hanging by a clean shutdown."""
    srv, _, _, rng = _server(grid)
    futs = [srv.submit(rng.standard_normal((32, 1)).astype(np.float32))
            for _ in range(4)]
    srv.start()
    srv.stop(drain=True)
    assert all(f.done() for f in futs)
    assert srv.stats()["served"] == 4 and srv.pending() == 0


def test_concurrency_stress_producers_vs_churn(grid):
    """N producer threads against ONE real drain loop while a churn
    thread replaces and evicts/re-admits slots: every future completes
    (served or typed-stranded, never a hang), counts conserve, and the
    compiled program never retraces."""
    n, C, panel_k = 32, 4, 4
    Ls, rng = _factors(C, n, seed=11)
    bank = api.FactorBank(grid, n, n0=8, capacity=C, dtype=np.float32)
    for L in Ls:
        bank.admit(L)
    solver = api.Solver.from_bank(bank)
    srv = api.AsyncSolveServer(solver, panel_k, queue_depth=16,
                               max_inflight=2).warmup()
    key = solver.program_for(panel_k).key
    traces = session.TRACE_COUNTS[key]
    N, per = 4, 25
    futures, shed = [], [0] * N
    flock = threading.Lock()
    barrier = threading.Barrier(N + 2)
    stop_churn = threading.Event()
    errors = []

    def producer(w):
        try:
            prng = np.random.default_rng(100 + w)
            barrier.wait()
            for i in range(per):
                b = prng.standard_normal((n, 1)).astype(np.float32)
                # steady slots 0/1 only; churn owns slots 2/3
                try:
                    f = srv.submit(b, factor=(w + i) % 2,
                                   tenant=f"w{w}")
                except api.Overloaded:
                    shed[w] += 1
                    continue
                with flock:
                    futures.append(f)
        except Exception as e:                # pragma: no cover
            errors.append(e)

    def churn():
        try:
            crng = np.random.default_rng(999)
            barrier.wait()
            while not stop_churn.is_set():
                slot = int(crng.integers(2, C))
                Lnew = (np.tril(crng.standard_normal((n, n)))
                        + n * np.eye(n)).astype(np.float32)
                if crng.integers(2):
                    bank.replace(slot, Lnew)  # generation-preserving
                else:
                    bank.evict(slot)
                    bank.admit(Lnew)          # turnover: strands queue
        except Exception as e:                # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=producer, args=(w,))
               for w in range(N)]
    threads.append(threading.Thread(target=churn))
    for t in threads:
        t.start()
    with srv:
        barrier.wait()
        for t in threads[:-1]:
            t.join(60)
        stop_churn.set()
        threads[-1].join(60)
        # submit a few against the churned slots too: they either
        # serve or strand with the typed error — never hang
        for slot in (2, 3):
            if bank.is_live(slot):
                try:
                    futures.append(srv.submit(
                        np.zeros((n, 1), np.float32), factor=slot))
                except (ValueError, api.Overloaded):
                    pass
    assert not errors
    assert all(f.done() for f in futures)     # stop(drain=True) above
    outcomes = [f.exception() for f in futures]
    assert all(e is None or isinstance(e, api.StrandedRequestError)
               for e in outcomes)
    st = srv.stats()
    assert st["served"] + st["stranded"] == len(futures)
    assert st["shed"] == sum(shed)            # count conservation
    # capacity churn NEVER recompiles the wave program
    assert session.TRACE_COUNTS[key] == traces


def test_update_waits_for_a_dispatch_reading_the_stacks(grid):
    """An update donates the stacks it reads, so it must never run
    between a solve's read of the stacks and its dispatch: the race
    the churn stress above can hit, made deterministic.  A replace
    called while ``with_stacks`` holds the stacks waits for it, and
    the program it held them for gets live buffers."""
    n, C = 32, 2
    Ls, rng = _factors(C, n, seed=5)
    bank = api.FactorBank(grid, n, n0=8, capacity=C, dtype=np.float32)
    for L in Ls:
        bank.admit(L)
    bank.replace(0, Ls[0])                    # the updater compiled
    held, release = threading.Event(), threading.Event()
    seen = []

    def dispatch(stacks):
        held.set()
        release.wait(30)
        seen.append(all(not a.is_deleted() for a in stacks))
        return jax.block_until_ready(stacks)

    solve = threading.Thread(target=bank.with_stacks, args=(dispatch,))
    solve.start()
    assert held.wait(30)
    churn = threading.Thread(target=bank.replace, args=(1, Ls[0]))
    churn.start()
    churn.join(0.5)
    assert churn.is_alive()                   # waiting on the dispatch
    release.set()
    solve.join(30)
    churn.join(30)
    assert seen == [True] and not churn.is_alive()
    assert bank.updates_dispatched == C + 2


# ------------------------- FairQueue unit tests -------------------------

def _req(seq, tenant="t", width=1):
    return _Request(seq=seq, b=None, width=width, tenant=tenant,
                    key=0, gen=0, order=32, future=None)


def test_fairqueue_width_bound_stops_at_first_nonfit():
    fq = FairQueue(panel_k=4, depth=16)
    for seq, w in enumerate([2, 3, 1]):       # 2 fits, 3 doesn't, STOP
        fq.push(_req(seq, width=w))
    wave = fq.pack()
    assert [r.seq for r in wave] == [0]       # no skip-ahead past #1
    assert [r.seq for r in fq.pack()] == [1, 2]


def test_fairqueue_wide_request_never_starves():
    """A panel-wide request pays its width (later virtual finish), but
    a CONTINUOUS stream of narrow competitors cannot starve it: its
    fixed tag becomes the minimum within a bounded number of waves,
    and it then packs alone into a fresh panel."""
    fq = FairQueue(panel_k=4, depth=64)
    fq.push(_req(0, "slow", width=4))
    seq, served = 1, []
    for _ in range(10):
        for _ in range(4):                    # keep the pressure on
            fq.push(_req(seq, "fast", width=1))
            seq += 1
        served.append([r.seq for r in fq.pack()])
        if [0] in served:
            break
    assert [0] in served[:3]                  # alone, within 3 waves


def test_fairqueue_depth_bound_and_idle_reset():
    fq = FairQueue(panel_k=4, depth=2)
    fq.push(_req(0))
    fq.push(_req(1))
    with pytest.raises(api.Overloaded, match="full"):
        fq.push(_req(2))
    fq.pack()
    assert fq._vclock == 0.0 and not fq._vt   # idle => WFQ state reset
    fq.push(_req(3))                          # and admission reopens
    assert len(fq) == 1


def test_fairqueue_pop_if_removes_matching_fifo():
    fq = FairQueue(panel_k=8, depth=16)
    for seq in range(6):
        fq.push(_req(seq, tenant="a" if seq % 2 else "b"))
    hit = fq.pop_if(lambda r: r.tenant == "a")
    assert [r.seq for r in hit] == [1, 3, 5]
    assert len(fq) == 3
    assert fq.pop_if(lambda r: False) == []


def test_fairqueue_pop_if_frees_width_and_depth():
    fq = FairQueue(panel_k=8, depth=2)
    fq.push(_req(0, width=3))
    fq.push(_req(1, width=2))
    assert fq.queued_width() == 5
    fq.pop_if(lambda r: r.seq == 0)
    assert fq.queued_width() == 2
    fq.push(_req(2))                  # depth slot freed by the pop
    assert [r.seq for r in fq.pack()] == [1, 2]
    assert fq.pop_if(lambda r: True) == []    # empty queue: no-op


def test_fairqueue_weight_update_under_churn():
    fq = FairQueue(panel_k=2, depth=64)
    seq = 0

    def burst(counts):
        nonlocal seq
        for t, c in counts:
            for _ in range(c):
                fq.push(_req(seq, t))
                seq += 1

    burst([("a", 2), ("b", 2)])
    drained = []
    while len(fq):
        drained.extend(fq.pack())
    # equal weights: the wave interleaves fairly
    assert sorted(r.tenant for r in drained[:2]) == ["a", "b"]
    fq.set_weight("a", 4.0)           # mid-stream reweigh
    with pytest.raises(ValueError, match="weight"):
        fq.set_weight("a", 0.0)
    burst([("a", 4), ("b", 4)])
    drained2 = []
    while len(fq):
        drained2.extend(fq.pack())
    # churn loses nothing, per-tenant FIFO holds, and the heavier
    # tenant now FRONT-LOADS the drain order
    assert len(drained2) == 8
    for t in ("a", "b"):
        mine = [r.seq for r in drained2 if r.tenant == t]
        assert mine == sorted(mine)
    first_half = [r.tenant for r in drained2[:4]]
    assert first_half.count("a") > first_half.count("b")


def test_server_set_weight_applies_to_live_and_future_queues(
        grid, fake_clock, drain_driver):
    srv, Ls, _, rng = _server(grid, clock=fake_clock,
                              weights={"a": 1.0, "b": 1.0})
    b = rng.standard_normal((32, 1)).astype(np.float32)
    f0 = srv.submit(b, factor=0, tenant="a")  # queue 0 exists now
    srv.set_weight("a", 8.0)
    with pytest.raises(ValueError, match="weight"):
        srv.set_weight("a", -1.0)
    assert srv._queues[0].weight("a") == 8.0  # live queue updated
    f1 = srv.submit(b, factor=1, tenant="a")  # queue 1 created after
    assert srv._queues[1].weight("a") == 8.0
    drain_driver(srv).run_until_idle()
    srv.flush()
    assert f0.exception(timeout=0) is None
    assert f1.exception(timeout=0) is None


def test_fairqueue_rejects_bad_config():
    with pytest.raises(ValueError, match="depth"):
        FairQueue(panel_k=4, depth=0)
    with pytest.raises(ValueError, match="weight"):
        FairQueue(panel_k=4, depth=4, weights={"t": 0.0})


def test_async_server_rejects_wrapping_a_solveserver(grid):
    Ls, _ = _factors(1)
    solver = api.Solver.from_factor(Ls[0], grid, n0=8)
    with pytest.raises(TypeError, match="directly"):
        api.AsyncSolveServer(api.SolveServer(solver, 4))
    with pytest.raises(ValueError, match="max_inflight"):
        api.AsyncSolveServer(solver, 4, max_inflight=0)
