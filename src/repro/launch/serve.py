"""Production serving CLI: batched prefill + decode on a mesh, or
TRSM solve serving against a device-resident factor.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --batch 4 --prompt-len 32 --new-tokens 16 [--mesh debug]

    # the paper's workload: repeated solves against a fixed factor,
    # served from cyclic device storage (zero steady-state transfers);
    # --precision picks the mixed-precision policy per workload
    # (bf16_refine = MXU-native sweep + on-device refinement to fp32)
    PYTHONPATH=src python -m repro.launch.serve --workload trsm \
        --n 256 --panel-k 16 --requests 64 [--p1 2 --p2 2] \
        [--precision fp32|bf16|bf16_refine|fp64_refine] [--cache-stats]

    # multi-factor batched serving: M resident factors (a FactorBank),
    # per-factor request queues, every wave = ONE dispatch covering all
    # M factors (per-layer preconditioners / per-tenant models)
    PYTHONPATH=src python -m repro.launch.serve --workload trsm-bank \
        --bank 16 --n 256 --panel-k 16 --requests 256 \
        [--map-mode vmap|scan] [--precision bf16_refine]

    # churn serving: a capacity-allocated LIVE-MUTABLE bank —
    # factors are replaced / evicted / re-admitted in place between
    # waves (KFAC-style re-factorization, tenant churn) while the ONE
    # compiled program keyed on the capacity keeps serving: zero
    # retraces, zero rebuilds (DESIGN.md Sec. 11)
    PYTHONPATH=src python -m repro.launch.serve --workload trsm-churn \
        --bank 16 --n 256 --panel-k 16 --requests 256 --updates 32 \
        [--precision bf16_refine] [--cache-stats]

    # mixed-order multi-tenant fleet: the capacity planner buckets a
    # spectrum of factor orders (zero-padding small orders into shared
    # banks where the modeled overhead is bought back by the saved
    # dispatch), requests route by (tenant, order), full buckets
    # reclaim their coldest slot across tenants (DESIGN.md Sec. 12)
    PYTHONPATH=src python -m repro.launch.serve --workload trsm-fleet \
        --n 256 --panel-k 16 --requests 256 --updates 16 \
        [--precision bf16_refine] [--fleet-stats] [--cache-stats]

    # open-loop async traffic: Poisson arrivals at --rate req/s against
    # the background drain loop (AsyncSolveServer) — bounded queues,
    # typed shedding, SolveFuture handles, p50/p99 + goodput against
    # the --slo-ms latency objective (DESIGN.md Sec. 13)
    PYTHONPATH=src python -m repro.launch.serve --workload trsm-traffic \
        --n 256 --panel-k 16 --requests 512 --rate 500 --slo-ms 50 \
        [--queue-depth 128] [--precision bf16_refine] [--cache-stats]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh, make_debug_mesh
from repro.models import lm
from repro.train import serve_step as ss


def _print_cache_stats():
    from repro import api
    st = api.default_cache().stats()
    print(f"compiled-solver cache: size={st['size']} hits={st['hits']} "
          f"misses={st['misses']} evictions={st['evictions']} "
          f"hit_rate={st['hit_rate']:.3f}")


def serve_trsm(args):
    """Serve TRSM solve requests against a device-resident factor."""
    from repro import api
    if args.precision == "fp64_refine":
        jax.config.update("jax_enable_x64", True)
    rng = np.random.default_rng(0)
    n = args.n
    L = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    if args.precision != "fp64_refine":
        L = L.astype(np.float32)
    structure = None
    if args.structure:
        # admission enforces the promise (masks L to the structure),
        # so serving a random dense factor under --structure is safe —
        # it solves against the masked operator (DESIGN.md Sec. 14)
        structure = api.FactorStructure.parse(args.structure, n=n)
    grid = api.make_trsm_mesh(args.p1, args.p2)
    solver = api.Solver.from_factor(L, grid, method=args.method,
                                    n0=args.n0, precision=args.precision,
                                    k_hint=args.panel_k,
                                    structure=structure,
                                    overlap=args.overlap)
    server = api.SolveServer(solver, args.panel_k).warmup()
    widths = rng.integers(1, args.panel_k + 1, args.requests)
    t0 = time.time()
    for w in widths:
        server.submit(jnp.asarray(rng.standard_normal((n, int(w)))))
    outs = server.drain()[0]
    if outs:
        jax.block_until_ready(outs[-1])
    dt = time.time() - t0
    panels = server.panels_solved
    policy = solver.policy
    print(f"served {server.requests_served} solve requests "
          f"({int(widths.sum())} columns) in {panels} panels, "
          f"{dt:.3f}s ({dt / max(panels, 1) * 1e3:.2f} ms/panel) "
          f"on grid p1={args.p1} p2={args.p2} n={n} "
          f"method={solver.method} precision={policy.name} "
          f"(sweep {policy.compute}, serve {policy.io_dtype.name}, "
          f"{policy.refine_steps} refine passes)")
    if args.cache_stats:
        _print_cache_stats()


def serve_trsm_bank(args):
    """Serve solve requests against a bank of M resident factors."""
    from repro import api
    if args.precision == "fp64_refine":
        jax.config.update("jax_enable_x64", True)
    rng = np.random.default_rng(0)
    n, M = args.n, args.bank
    Ls = np.stack([np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
                   for _ in range(M)])
    if args.precision != "fp64_refine":
        Ls = Ls.astype(np.float32)
    grid = api.make_trsm_mesh(args.p1, args.p2)
    solver = api.Solver.from_factors(Ls, grid, method=args.method,
                                     n0=args.n0,
                                     precision=args.precision,
                                     map_mode=args.map_mode,
                                     overlap=args.overlap)
    server = api.SolveServer(solver, args.panel_k).warmup()
    widths = rng.integers(1, args.panel_k + 1, args.requests)
    t0 = time.time()
    for i, w in enumerate(widths):
        server.submit(rng.standard_normal((n, int(w))), int(i % M))
    outs = server.drain()
    jax.block_until_ready([x for xs in outs.values() for x in xs])
    dt = time.time() - t0
    waves = server.waves_solved
    policy = solver.policy
    print(f"served {server.requests_served} solve requests "
          f"({int(widths.sum())} columns) against {M} factors in "
          f"{waves} waves (one dispatch per wave, {M} solves each), "
          f"{dt:.3f}s ({dt / max(waves, 1) * 1e3:.2f} ms/wave, "
          f"{dt / max(waves * M, 1) * 1e3:.3f} ms/solve) on grid "
          f"p1={args.p1} p2={args.p2} n={n} "
          f"map_mode={solver.bank.map_mode} "
          f"precision={policy.name} ({policy.refine_steps} refine passes)")
    if args.cache_stats:
        _print_cache_stats()


def serve_trsm_churn(args):
    """Serve against a capacity-allocated live-mutable bank while the
    factor population churns: replace / evict / re-admit between
    waves, one compiled program (keyed on capacity) throughout."""
    from repro import api
    from repro.core import session
    if args.precision == "fp64_refine":
        jax.config.update("jax_enable_x64", True)
    rng = np.random.default_rng(0)
    n, C = args.n, args.bank
    dt = np.float64 if args.precision == "fp64_refine" else np.float32

    def fresh():
        return (np.tril(rng.standard_normal((n, n)))
                + n * np.eye(n)).astype(dt)

    grid = api.make_trsm_mesh(args.p1, args.p2)
    bank = api.FactorBank(grid, n, method=args.method, n0=args.n0,
                          precision=args.precision,
                          dtype=None if args.precision else dt,
                          map_mode=args.map_mode, capacity=C,
                          overlap=args.overlap)
    solver = api.Solver.from_bank(bank)
    server = api.SolveServer(solver, args.panel_k).warmup()  # EMPTY warmup
    for _ in range(max(C // 2, 1)):          # start at half occupancy
        bank.admit(fresh())

    key = solver.spec_for(args.panel_k)
    uspec = bank.update_spec()
    traces0 = (session.TRACE_COUNTS[key], session.TRACE_COUNTS[uspec])

    widths = rng.integers(1, args.panel_k + 1, args.requests)
    per_wave = max(args.requests // max(args.updates, 1), 1)
    replaced = evicted = 0
    t_update = 0.0
    t0 = time.time()
    for i, w in enumerate(widths):
        live = bank.live_slots()
        server.submit(rng.standard_normal((n, int(w))).astype(dt),
                      int(live[i % len(live)]))
        if (i + 1) % per_wave == 0:
            outs = server.drain()
            jax.block_until_ready([x for xs in outs.values() for x in xs])
            # churn between waves: refresh one slot in place, and
            # periodically turn a slot over (evict -> re-admit)
            live = bank.live_slots()
            tu = time.time()
            bank.replace(int(live[replaced % len(live)]), fresh())
            replaced += 1
            if replaced % 3 == 0:
                victim = int(live[evicted % len(live)])
                bank.evict(victim)
                slot = bank.admit(fresh())
                if slot != victim:         # lowest-free-slot reuse
                    raise AssertionError((slot, victim))
                evicted += 1
            jax.block_until_ready(bank.factors_cyclic)
            t_update += time.time() - tu
    outs = server.drain()
    jax.block_until_ready([x for xs in outs.values() for x in xs])
    dt_total = time.time() - t0
    retraced = (session.TRACE_COUNTS[key] - traces0[0],
                session.TRACE_COUNTS[uspec] - traces0[1])
    # one compiled scatter per replace and per re-admit (evict itself
    # is host-side bookkeeping)
    updates = replaced + evicted
    policy = solver.policy
    print(f"served {server.requests_served} solve requests in "
          f"{server.waves_solved} waves against a capacity-{C} bank "
          f"(occupancy {bank.size}) with {updates} in-place updates "
          f"({replaced} replaces, {evicted} evict+readmit), "
          f"{dt_total:.3f}s total, "
          f"{t_update / max(updates, 1) * 1e3:.2f} ms/update; "
          f"retraces solve={retraced[0]} update={retraced[1]} "
          f"(steady state: 0/0) on grid p1={args.p1} p2={args.p2} n={n} "
          f"precision={policy.name}")
    if args.cache_stats:
        _print_cache_stats()


def serve_trsm_fleet(args):
    """Mixed-order multi-tenant serving through the fleet tier: the
    planner buckets the order spectrum, two tenants' factors land in
    planner-chosen buckets, requests route by (tenant, order), churn
    refreshes factors in place, and over-subscribed buckets reclaim
    their coldest slot across tenants (DESIGN.md Sec. 12)."""
    from repro import api
    from repro.core import session
    if args.precision == "fp64_refine":
        jax.config.update("jax_enable_x64", True)
    rng = np.random.default_rng(0)
    n = args.n
    dt = np.float64 if args.precision == "fp64_refine" else np.float32
    orders = [n, n // 2, n // 4]        # the tenants' order spectrum

    def fresh(d):
        return (np.tril(rng.standard_normal((d, d)))
                + d * np.eye(d)).astype(dt)

    grid = api.make_trsm_mesh(args.p1, args.p2)
    # two tenants, two factors per order each
    manifest = {d: 4 for d in orders}
    plan = api.plan_fleet(manifest, grid, k=args.panel_k,
                          precision=args.precision, dtype=None
                          if args.precision else dt,
                          overlap=args.overlap)
    print(plan.table())
    fleet = api.SolverFleet(grid, plan)
    handles = {}
    for tenant in ("tenant-a", "tenant-b"):
        for d in orders:
            for j in range(2):
                tag = f"layer{orders.index(d)}-{j}"
                handles[(tenant, tag)] = fleet.admit(
                    fresh(d), tenant=tenant, tag=tag)
    server = api.SolveServer(fleet, args.panel_k).warmup()

    solve_keys = [fleet.solver(key).spec_for(args.panel_k)
                  for key in fleet.buckets]
    traces0 = sum(session.TRACE_COUNTS[k] for k in solve_keys)

    widths = rng.integers(1, args.panel_k + 1, args.requests)
    per_wave = max(args.requests // max(args.updates, 1), 1)
    keys = list(handles)
    replaced = reclaimed = 0
    t0 = time.time()
    for i, w in enumerate(widths):
        tenant, tag = keys[i % len(keys)]
        h = handles[(tenant, tag)]
        server.submit(rng.standard_normal((h.order, int(w))).astype(dt),
                      tenant=tenant, tag=tag)
        if (i + 1) % per_wave == 0:
            outs = server.drain()
            jax.block_until_ready([x for xs in outs.values()
                                   for x in xs])
            # churn between waves: refresh one factor in place; every
            # third update over-subscribes a bucket so the fleet
            # reclaims its coldest slot cross-tenant
            tenant, tag = keys[replaced % len(keys)]
            h = handles[(tenant, tag)]
            fleet.replace(h, fresh(h.order))
            replaced += 1
            if replaced % 3 == 0:
                d = orders[reclaimed % len(orders)]
                hot = fleet.admit(fresh(d), tenant="tenant-c",
                                  tag=f"burst{reclaimed}")
                reclaimed += 1
                # drop stale handles the reclaim displaced
                handles = {kt: hh for kt, hh in handles.items()
                           if hh is not hot and any(
                               hh is cur for cur in fleet.handles())}
                handles[("tenant-c", hot.tag)] = hot
                keys = list(handles)
    outs = server.drain()
    jax.block_until_ready([x for xs in outs.values() for x in xs])
    dt_total = time.time() - t0
    retraced = sum(session.TRACE_COUNTS[k]
                   for k in solve_keys) - traces0
    st = fleet.stats()
    print(f"served {server.requests_served} mixed-order requests "
          f"({len(orders)} orders, {len(fleet.buckets)} planned "
          f"bucket(s)) in {server.waves_solved} bucket-waves, "
          f"{dt_total:.3f}s; {replaced} in-place refreshes, "
          f"{st['reclaims']} cross-tenant reclaims; "
          f"retraces solve={retraced} (steady state: 0) on grid "
          f"p1={args.p1} p2={args.p2}")
    if args.fleet_stats:
        print(fleet.format_stats())
    if args.cache_stats:
        _print_cache_stats()


def serve_trsm_traffic(args):
    """Open-loop async serving: Poisson arrivals against the
    background drain loop, futures resolved as waves finalize, tail
    latency reported against the --slo-ms objective.

    ``--admission slo`` runs the SLO-aware admission controller
    (requests whose estimated queue wait cannot meet --slo-ms are shed
    at submit with DeadlineUnmeetable, surfaced through the future);
    ``--autoscale`` serves a mixed-order FLEET instead of a flat bank
    and attaches the planner-driven Autoscaler (DESIGN.md Sec. 15)."""
    import json

    from repro import api
    if args.precision == "fp64_refine":
        jax.config.update("jax_enable_x64", True)
    rng = np.random.default_rng(0)
    n, M = args.n, min(args.bank, 4)
    dt = np.float64 if args.precision == "fp64_refine" else np.float32
    grid = api.make_trsm_mesh(args.p1, args.p2)

    def fresh(d):
        return (np.tril(rng.standard_normal((d, d)))
                + d * np.eye(d)).astype(dt)

    admission = api.AdmissionController(slo_ms=args.slo_ms) \
        if args.admission == "slo" else None
    if args.autoscale:
        # mixed-order fleet: half the factors at n, half at n // 2 —
        # the spectrum the autoscaler splits/merges under load drift
        orders = [n] * max(M // 2, 1) + [n // 2] * max(M // 2, 1)
        manifest = {}
        for d in orders:
            manifest[d] = manifest.get(d, 0) + 1
        plan = api.plan_fleet(manifest, grid, k=args.panel_k,
                              precision=args.precision,
                              dtype=None if args.precision else dt,
                              overlap=args.overlap)
        fleet = api.SolverFleet(grid, plan)
        tags = []
        for j, d in enumerate(orders):
            tag = f"f{j}"
            fleet.admit(fresh(d), tenant="traffic", tag=tag)
            tags.append((tag, d))
        server = api.AsyncSolveServer(
            fleet, args.panel_k, queue_depth=args.queue_depth,
            slo_ms=args.slo_ms).warmup()
        scaler = api.Autoscaler(server)
        policy = fleet.solver(next(iter(fleet.buckets))).policy
    else:
        Ls = np.stack([fresh(n) for _ in range(M)])
        solver = api.Solver.from_factors(Ls, grid, method=args.method,
                                         n0=args.n0,
                                         precision=args.precision,
                                         overlap=args.overlap)
        server = api.AsyncSolveServer(
            solver, args.panel_k, queue_depth=args.queue_depth,
            slo_ms=args.slo_ms).warmup()
        scaler = None
        policy = solver.policy
    width = max(args.panel_k // 4, 1)
    pools = {d: [jnp.asarray(rng.standard_normal((d, width))
                             .astype(dt)) for _ in range(32)]
             for d in ({n, n // 2} if args.autoscale else {n})}
    jax.block_until_ready(list(pools.values()))

    def sub(i, d=None):
        if args.autoscale:
            tag, order = tags[i % len(tags)]
            return server.submit(pools[order][i % 32],
                                 tenant="traffic", tag=tag)
        return server.submit(pools[n][i % 32], factor=i % M)

    # prime every wave composition before the clock starts: lazy
    # first compiles belong to startup, not to the measured traffic
    per_wave = M * max(args.panel_k // width, 1)
    for count in range(1, per_wave + 1):
        for i in range(count):
            sub(i)
        while server.pending() or server._inflight:
            server.step()
        server.flush()
    # admission goes live only now: priming compiles must not feed
    # the controller's service estimates
    server.reset_service_ewma()
    if admission is not None:
        server.set_admission(admission)
    gaps = rng.exponential(1.0 / args.rate, size=args.requests)
    shed = 0
    futs = []
    t0 = time.monotonic()
    sched = t0 + np.cumsum(gaps)
    with server:                       # background drain loop
        for i, t_i in enumerate(sched):
            delay = t_i - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            try:
                futs.append((t_i, sub(i)))
            except api.Overloaded:
                shed += 1              # depth shed (raised at submit)
        served, deadline_shed = [], 0
        for t_i, f in futs:
            try:
                f.result(timeout=120)
                served.append((t_i, f))
            except api.DeadlineUnmeetable:
                deadline_shed += 1     # SLO shed (through the future)
    elapsed = time.monotonic() - t0
    lat = np.asarray([f.completed for _, f in served]) \
        - np.asarray([t for t, _ in served])
    violations = int((lat * 1e3 > args.slo_ms).sum())

    def pct(q):
        return f"{np.percentile(lat, q) * 1e3:.2f}" if len(lat) \
            else "n/a"
    print(f"served {len(served)}/{args.requests} open-loop requests "
          f"(offered {args.rate:.0f} rps, goodput "
          f"{len(served) / elapsed:.0f} rps) in "
          f"{server.stats()['waves']} waves; p50 "
          f"{pct(50)} ms p99 "
          f"{pct(99)} ms vs SLO "
          f"{args.slo_ms:.0f} ms ({violations} violations); "
          f"shed {shed} at depth {args.queue_depth} + "
          f"{deadline_shed} at admission ({args.admission}) on grid "
          f"p1={args.p1} p2={args.p2} n={n} "
          f"precision={policy.name}")
    if scaler is not None:
        print(f"autoscaler: {len(scaler.replans)} replan(s) "
              + "".join(f"[{r['kind']}: {r['moved']} moved] "
                        for r in scaler.replans)
              + f"buckets now "
                f"{sorted(k[0] for k in server.fleet.buckets)}")
    if args.stats_json:
        st = server.stats()
        if scaler is not None:
            st["autoscaler"] = scaler.stats()
        if admission is not None:
            st["admission"] = admission.stats()
        print(json.dumps(st, default=str, sort_keys=True))
    if args.cache_stats:
        _print_cache_stats()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lm",
                    choices=["lm", "trsm", "trsm-bank", "trsm-churn",
                             "trsm-fleet", "trsm-traffic"])
    ap.add_argument("--arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="debug",
                    choices=["single", "multi", "debug"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    # trsm workload
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--n0", type=int, default=None)
    ap.add_argument("--panel-k", type=int, default=16)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--p1", type=int, default=1)
    ap.add_argument("--p2", type=int, default=1)
    ap.add_argument("--structure", default=None,
                    metavar="dense|banded[:BW]|block-sparse",
                    help="factor block structure for the trsm workload "
                         "(level-scheduled sweep; DESIGN.md Sec. 14)")
    ap.add_argument("--overlap", default="auto",
                    choices=["auto", "on", "off"],
                    help="software-pipeline the steady-state sweep "
                         "(prefetch the next panel's collectives under "
                         "this panel's compute; bit-identical results; "
                         "DESIGN.md Sec. 16)")
    ap.add_argument("--method", default="inv",
                    choices=["inv", "rec", "auto"])
    ap.add_argument("--bank", type=int, default=16,
                    help="factor count M for the trsm-bank workload "
                         "(= capacity C for trsm-churn)")
    ap.add_argument("--updates", type=int, default=32,
                    help="in-place bank updates interleaved with the "
                         "waves (trsm-churn workload)")
    ap.add_argument("--rate", type=float, default=500.0,
                    help="offered Poisson arrival rate in req/s "
                         "(trsm-traffic workload)")
    ap.add_argument("--slo-ms", type=float, default=50.0,
                    help="latency objective: completions slower than "
                         "this count as SLO violations (trsm-traffic)")
    ap.add_argument("--queue-depth", type=int, default=128,
                    help="per-slot bounded queue depth; submits beyond "
                         "it are shed with Overloaded (trsm-traffic)")
    ap.add_argument("--admission", default="depth",
                    choices=["depth", "slo"],
                    help="admission policy for trsm-traffic: 'depth' "
                         "sheds only on full queues; 'slo' also sheds "
                         "requests whose estimated queue wait cannot "
                         "meet --slo-ms (DeadlineUnmeetable through "
                         "the future; DESIGN.md Sec. 15)")
    ap.add_argument("--autoscale", action="store_true",
                    help="serve a mixed-order fleet with the "
                         "planner-driven Autoscaler attached: bucket "
                         "splits/merges follow offered-load drift "
                         "(trsm-traffic; DESIGN.md Sec. 15)")
    ap.add_argument("--stats-json", action="store_true",
                    help="dump one machine-readable JSON line of "
                         "server (+ admission/autoscaler) stats after "
                         "the run (trsm-traffic)")
    ap.add_argument("--map-mode", default="vmap",
                    choices=["vmap", "scan"],
                    help="how the bank program maps the factor axis")
    ap.add_argument("--precision", default=None,
                    choices=["fp32", "bf16", "bf16_refine", "fp64_refine"],
                    help="mixed-precision policy for the trsm workload "
                         "(default: uniform at the factor dtype)")
    ap.add_argument("--cache-stats", action="store_true",
                    help="print compiled-solver cache stats (hits/misses"
                         "/evictions/hit rate) after the drain")
    ap.add_argument("--fleet-stats", action="store_true",
                    help="print fleet-wide serving stats (per-bucket "
                         "occupancy, hit rate, reclaim count) after the "
                         "drain (trsm-fleet workload)")
    args = ap.parse_args()
    use_compile_cache()

    if args.workload == "trsm":
        return serve_trsm(args)
    if args.workload == "trsm-bank":
        return serve_trsm_bank(args)
    if args.workload == "trsm-churn":
        return serve_trsm_churn(args)
    if args.workload == "trsm-fleet":
        return serve_trsm_fleet(args)
    if args.workload == "trsm-traffic":
        return serve_trsm_traffic(args)
    if not args.arch:
        ap.error("--arch is required for the lm workload")

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get(args.arch)
    if args.mesh == "debug":
        n = len(jax.devices())
        mesh = make_debug_mesh(max(n // 4, 1), min(4, n))
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    B = args.batch
    max_seq = args.prompt_len + args.new_tokens
    params = lm.init(cfg, jax.random.key(0))
    cache = lm.init_cache(cfg, B, max_seq)
    decode = ss.jit_decode_step(cfg, mesh, params, cache, B)

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab,
                                       (B, args.prompt_len)))
    t0 = time.time()
    # prefill IS a decode step with S = prompt length (same code path)
    logits, cache = lm.decode_step(params, cfg, prompts, cache)
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    out = [tok]
    for _ in range(args.new_tokens - 1):
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits[:, -1:], axis=-1)
        out.append(tok)
    gen = np.asarray(jnp.concatenate(out, axis=1))
    dt = time.time() - t0
    for b in range(B):
        print(f"seq {b}: {gen[b, :12].tolist()}")
    print(f"{B * args.new_tokens} tokens in {dt:.2f}s "
          f"({B * args.new_tokens / dt:.1f} tok/s) on mesh "
          f"{dict(mesh.shape)}")


if __name__ == "__main__":
    main()
