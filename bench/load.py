"""The one traffic generator.  A mix is a data file under
``bench/traffic/`` whose ``entry`` names one of the loops in ``ENTRIES``;
everything else in the file parametrizes that loop.  A loop drives the
system of the cell (``bench/run.py``) through the hooks it names, so a
new mix over an existing loop is a data file alone.  Every loop is
closed: a caller sends its next request only when its last one is back,
and the window's clock is ``time.monotonic`` (the serving tier's own
clock, so a future's completion stamp reads on it).

* ``async_server`` -- ``callers`` clients, each with one request of
  ``cols`` columns outstanding, through ``system.submit`` (a
  SolveFuture) and ``SolveFuture.result``; ``client_threads`` threads
  (default 1) share the callers.  Hooks: ``submit(b)``,
  ``rhs_for(i) -> (b, pool index)``.
* ``refresh_solve`` -- optimizer steps: ``refresh_per_step`` factors
  refreshed and waited on, then one preconditioning of the whole
  gradient set, waited on.  Hooks: ``factors``, ``versions``,
  ``refresh(factor, version) -> [seconds of each replace_factor]``,
  ``solve() -> answers`` (not yet waited on), ``step_cols``.

Both run until ``seconds`` have passed, then wait for what is still
outstanding (up to ``WAIT_S`` past the close).  The window runs from the
first request sent to the last answer back, and counts all the work in
it.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time

import numpy as np

WAIT_S = 60.0
now = time.monotonic


def span(name: str):
    """A host span in the profiler's trace (a no-op cost when no trace
    is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Reservoir:
    """A uniform sample, drawn from the seed, of the answers of a
    window: ``size`` answers kept, each reduced by ``take`` (a device
    op, dispatched only for answers that are kept)."""

    def __init__(self, size: int, seed: int, take):
        self.size = size
        self.take = take
        self.rng = np.random.default_rng(seed)
        self.items: list = []
        self.seen = 0
        self.lock = threading.Lock()

    def offer(self, tag, X) -> None:
        with self.lock:
            self.seen += 1
            if len(self.items) < self.size:
                self.items.append((tag, self.take(X)))
                return
            j = int(self.rng.integers(0, self.seen))
            if j < self.size:
                self.items[j] = (tag, self.take(X))


def drive(system, p: dict, seconds: float, sample: Reservoir) -> dict:
    """Run the loop the mix names."""
    return ENTRIES[p["entry"]](system, p, seconds, sample)


def async_server(system, p: dict, seconds: float,
                 sample: Reservoir) -> dict:
    """Closed loop of ``p["callers"]`` clients on
    ``p.get("client_threads", 1)`` threads."""
    numbers = itertools.count()
    t0 = now()
    deadline = t0 + seconds

    def client(callers: int, out: dict) -> None:
        outstanding: collections.deque = collections.deque()
        lat, lags = [], []
        cols = failed = 0
        t_end = t0

        def issue():
            i = next(numbers)
            b, pidx = system.rhs_for(i)
            with span("bench.submit"):
                t = now()
                fut = system.submit(b)
            outstanding.append((i, pidx, t, fut))

        for _ in range(callers):
            issue()
        while outstanding:
            i, pidx, t, fut = outstanding.popleft()
            with span("bench.wait"):
                try:
                    X = fut.result(
                        timeout=max(deadline + WAIT_S - now(), 1e-3))
                except Exception:   # lost or failed: counts as missing
                    X = None
            if X is None or fut.completed is None:
                failed += 1
            else:
                t_end = max(t_end, fut.completed)
                lat.append(fut.completed - t)
                cols += p["cols"]
                sample.offer((i, pidx), X)
            if now() < deadline:
                if fut.completed is not None:
                    lags.append(now() - fut.completed)
                issue()
        out.update(lat=lat, lags=lags, cols=cols, failed=failed,
                   t_end=t_end)

    threads = p.get("client_threads", 1)
    share = [p["callers"] // threads + (t < p["callers"] % threads)
             for t in range(threads)]
    outs = [{} for _ in share]
    if threads == 1:
        client(share[0], outs[0])
    else:
        pool = [threading.Thread(target=client, args=(c, o),
                                 name=f"bench-client-{t}")
                for t, (c, o) in enumerate(zip(share, outs))]
        for th in pool:
            th.start()
        for th in pool:
            th.join()
    return dict(t0=t0, t_end=max(o["t_end"] for o in outs),
                attempted=next(numbers),
                failed=sum(o["failed"] for o in outs),
                cols=sum(o["cols"] for o in outs),
                latencies=[x for o in outs for x in o["lat"]],
                lags=[x for o in outs for x in o["lags"]])


def refresh_solve(system, p: dict, seconds: float,
                  sample: Reservoir) -> dict:
    """Optimizer steps.  Step i refreshes the next ``refresh_per_step``
    factors, round robin, each to its next version, then solves."""
    import jax
    holds = [0] * system.factors
    refresh_s = []
    steps = refreshes = 0
    t0 = now()
    deadline = t0 + seconds
    t_end = t0
    while steps == 0 or now() < deadline:
        for _ in range(p["refresh_per_step"]):
            f = refreshes % system.factors
            refreshes += 1
            version = (holds[f] + 1) % system.versions
            with span("bench.refresh"):
                refresh_s += system.refresh(f, version)
            holds[f] = version
        with span("bench.solve"):
            X = system.solve()
        sample.offer(tuple(holds), X)
        with span("bench.wait"):
            jax.block_until_ready(X)
        del X
        t_end = now()
        steps += 1
    return dict(t0=t0, t_end=t_end, attempted=steps, failed=0,
                cols=steps * system.step_cols, refresh_s=refresh_s,
                steps=steps)


ENTRIES = {"async_server": async_server, "refresh_solve": refresh_solve}


@contextlib.contextmanager
def counting_compiles():
    """Count the compiles and traces JAX reports while the block runs."""
    import jax
    seen = collections.Counter()

    def listen(event, *_a, **_k):
        if "backend_compile" in event:
            seen["compiles"] += 1
        elif "jaxpr_trace" in event:
            seen["traces"] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
