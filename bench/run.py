#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload hplmxp_n32768.block --seed 7 \\
        --seconds 20 --trace 0
    JAX_PLATFORMS=cpu python3 bench/run.py --workload kfac_granite8b.step \\
        --seed 7 --seconds 2 --trace 1 --cpu-rehearsal   # tiny sizes

A cell names a configuration (``bench/configs/<config>.json``: sizes,
precision, mesh, factor recipe, limits) and a traffic mix
(``bench/traffic/<mix>.json``, driven by ``bench/load.py``).  The run
makes its inputs on the device from ``--seed``, admits them through the
front door (``repro.api``), warms up the shapes its traffic uses,
measures for ``--seconds``, then checks a sample of the window's
answers, drawn from the seed, against the float64 reference
(``bench/reference.py``).  With ``--trace 1`` a profiler trace of a
shorter window (the mix's ``trace_seconds``) gives the per-layer
metrics, each read by ``bench/metrics/<name>.py``.

The last line of standard output is one JSON object (correct,
attempted, failed, metrics, device[, breakdown], checked); the numbers
compared are also the last lines of standard error.  Without a TPU the
run exits non-zero and prints no result, unless ``--cpu-rehearsal``
asks for the tiny sizes of each file's ``rehearsal`` block on whatever
JAX finds.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse          # noqa: E402
import functools         # noqa: E402
import gc                # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import pathlib           # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
import types             # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if sys.path and pathlib.Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)      # bench/trace.py must not shadow the stdlib
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np       # noqa: E402

from bench import data, load, reference, trace, work   # noqa: E402

now = time.monotonic


def log(*a) -> None:
    print(*a, flush=True)


class Refused(Exception):
    """The run cannot be made here (no chip, missing files): exit
    non-zero with no result."""


# ------------------------------ the cell ------------------------------

def _apply(d: dict, over: dict) -> None:
    for key, v in over.items():
        *path, last = key.split(".")
        node = d
        for part in path:
            node = node[part]
        node[last] = v


def load_cell(name: str, rehearsal: bool) -> dict:
    try:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
        cell = next(w for w in spec["workloads"] if w["name"] == name)
        entry = next(c for c in spec["configs"]
                     if c["name"] == cell["config"])
        cfg = json.loads((ROOT / entry["file"]).read_text())
        mix = json.loads((ROOT / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    except (OSError, StopIteration, KeyError, ValueError) as e:
        raise Refused(f"cannot resolve workload {name!r}: {e!r}")
    if rehearsal:
        _apply(cfg, cfg.get("rehearsal", {}))
        _apply(mix, mix.get("rehearsal", {}))

    def mine(m):
        return name in m.get("workloads", [name])
    return dict(cell=cell, cfg=cfg, mix=mix,
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


# ------------------------------ systems ------------------------------

class Dense:
    """One resident dense factor behind ``AsyncSolveServer``."""

    def __init__(self, api, jax, cfg, mix, seed, devices, control):
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.api, self.jax, self.cfg, self.mix = api, jax, cfg, mix
        self.seed, self.n = seed, cfg["n"]
        control = control or {}
        self.precision = control.get("precision", cfg["precision"])
        if isinstance(self.precision, dict):    # the preset, changed
            import dataclasses
            from repro.core.precision import PRESETS
            self.precision = dataclasses.replace(
                PRESETS[cfg["precision"]], **self.precision)
        self._undo = None
        if control.get("residual") == "high":
            self._residual_high()
        p1, p2 = cfg["mesh"]
        grid = api.make_trsm_mesh(p1, p2, devices[:p1 * p1 * p2])
        bank = api.FactorBank(grid, self.n, precision=self.precision,
                              n0=cfg.get("n0"))
        if cfg.get("ingest", "natural") == "cyclic":
            # made in the cyclic storage admission would produce, so no
            # chip ever holds the factor whole
            held = [data.dense_factor(
                self.seed, self.n,
                NamedSharding(grid.mesh, grid.spec_L()), p1, p1 * p2)]
            admit = bank.admit_cyclic
        else:
            held = [data.dense_factor(self.seed, self.n, NamedSharding(
                grid.mesh, P(("x", "y", "z"), None)))]
            admit = bank.admit
        jax.block_until_ready(held[0])
        t = now()
        admit(held.pop())               # admission frees it after the gather
        jax.block_until_ready(bank.stacks())
        self.admit_s = now() - t
        self.solver = api.Solver.from_bank(bank)
        self.server = api.AsyncSolveServer(
            self.solver, panel_k=mix["panel_k"],
            queue_depth=max(64, 2 * mix["callers"]), max_inflight=2)
        self.server.warmup()
        self.plan = (f"method={self.solver.method} n0={self.solver.n0} "
                     f"phase1={bank._phase1_mode} mesh=({p1},{p2}) "
                     f"precision={getattr(self.precision, 'name', self.precision)}"
                     f"{' (control ' + str(control) + ')' if control else ''} "
                     f"panel_k={mix['panel_k']}")
        cols, size = mix["cols"], mix["rhs_pool"]
        if mix["rhs_on"] == "device":
            sh = NamedSharding(grid.mesh, P(None, "z"))
            self.pool = [data.rhs(seed, i, self.n, cols, sh)
                         for i in range(size)]
        else:                           # host vectors, one per request
            self.pool = np.ascontiguousarray(np.asarray(
                data.rhs(seed, 0, self.n, size)).T)
        rng = np.random.default_rng([seed, 1])
        self.idx = np.sort(rng.choice(cols, min(cols, mix["check_cols"]),
                                      replace=False))
        if len(self.idx) == cols:
            self.take = lambda X: X
        else:
            self.take = jax.jit(lambda X: X[:, self.idx])
        self.programs = {"solve": "jit_" + self.solver.program_for(
            mix["panel_k"]).solve_donating.__name__}

    def _residual_high(self):
        """A look (``looks`` in the configuration): the refinement
        residual's GEMM at three bf16 passes (Precision.HIGH; written
        out on the CPU, which ignores the flag) in place of the
        program's six; the rest of the program as it is.  Programs
        built before or after are not shared with it."""
        from repro.core import refine, session
        from repro.core import grid as gridlib
        orig = refine.apply_cyclic_operator

        if self.jax.default_backend() == "cpu":
            dot = reference.dot_high        # the CPU ignores the flag
        else:                               # no split copies of L in HBM
            dot = functools.partial(self.jax.numpy.matmul,
                                    precision=self.jax.lax.Precision.HIGH)

        def apply_high(L_cyc, X, *, p1, p2, reverse, accum_dtype=None):
            Xg = gridlib.cyclic_rows_device(X, p1 * p2, reverse=reverse)
            Y = dot(L_cyc, Xg.astype(L_cyc.dtype))
            return gridlib.cyclic_rows_device(Y, p1, inverse=True,
                                              reverse=reverse)
        refine.apply_cyclic_operator = apply_high
        session.default_cache().clear()

        def undo():
            refine.apply_cyclic_operator = orig
            session.default_cache().clear()
        self._undo = undo

    def submit(self, b):
        return self.server.submit(b)

    def rhs_for(self, i):
        j = i % len(self.pool)
        return self.pool[j], j

    def warmup(self) -> None:
        """The wave shapes the mix forms: a wave of w requests for each
        w up to the mix's ``warm_fills`` (default: every w that fits a
        panel), since each fill is its own concatenate and filler slice
        in the front door; answers taken as in the window."""
        srv, cols, pk = self.server, self.mix["cols"], self.mix["panel_k"]
        waves = []
        for w in range(1, self.mix.get("warm_fills", pk // cols) + 1):
            waves.append([srv.submit(self.rhs_for(i)[0]) for i in range(w)])
            srv.step()
            if len(waves) > srv.max_inflight:     # done: drop the answers
                for f in waves.pop(0):
                    f.result(timeout=600)
        srv.flush()
        self.jax.block_until_ready(self.take(waves[-1][0].result()))
        self.server.start()

    def window(self, seconds: float) -> dict:
        waves0 = self.server.waves
        sample = load.Reservoir(self.mix["check_answers"], self.seed,
                                self.take)
        res = load.drive(self, self.mix, seconds, sample)
        res["sample"] = sample
        waves = self.server.waves - waves0
        n, c = self.n, res["cols"]
        res["counters"] = dict(cols=c, waves=waves,
                               panel_k=self.mix["panel_k"])
        # the factor read once in the window, each column's B and X once
        res["work"] = dict(W=work.solve_flops(n, c), Q=work.solve_bytes(
            n, c, self.cfg["factor_bytes"], self.cfg["io_bytes"]))
        return res

    def free(self) -> None:
        self.server.stop(drain=True)
        del self.server, self.solver
        if self._undo is not None:
            self._undo()
        gc.collect()

    def answers(self, sample):
        """[(X, B)] on the host for each kept answer."""
        out = []
        for (i, j), X in sample.items:
            X = np.asarray(X)
            if isinstance(self.pool, np.ndarray):
                B = self.pool[j][:, None]
            else:
                B = np.asarray(self.take(self.pool[j]))
            out.append((X, B))
        return out

    def check(self, pairs) -> list:
        """Backward errors, the factor made again on the device chunk by
        chunk (the solver is gone by now)."""
        good = [(X, B) for X, B in pairs if X.shape == B.shape]
        bad = len(pairs) - len(good)
        if not good:
            return [float("inf")] * bad
        X = np.concatenate([x for x, _ in good], axis=1)
        B = np.concatenate([b for _, b in good], axis=1)
        edges = np.cumsum([0] + [x.shape[1] for x, _ in good])
        rows = max(data.ROWS, (1 << 27) // (4 * self.n) // data.ROWS
                   * data.ROWS)

        def chunks():
            for r0 in range(0, self.n, rows):
                cnt = min(rows, self.n - r0)
                yield r0, np.asarray(
                    data.dense_factor_rows(self.seed, self.n, r0, cnt))
        return reference.backward_errors(chunks(), X, B, edges) \
            + [float("inf")] * bad


class Kfac:
    """One pipeline stage's K-FAC factors: each damped Cholesky factor L
    applied as an SPD solve, L then L^T, to the gradient columns it
    preconditions.  Factors of one order and column count share a pair
    of capacity banks (forward, transposed); each step refreshes
    factors in both banks, then preconditions every gradient."""

    def __init__(self, api, jax, cfg, mix, seed, devices, control):
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.api, self.jax, self.cfg, self.mix = api, jax, cfg, mix
        self.seed = seed
        f = cfg["factor"]
        self.versions = f["versions"]
        grid = api.make_trsm_mesh(1, 1, devices[:1])
        nat = NamedSharding(grid.mesh, P(None, None))
        kinds = cfg["factor_kinds"]
        # factor i = (layer, kind); factors of one (order, cols) group
        # are the slots of that group's banks, in factor order
        self.where, groups = [], {}
        for layer in range(cfg["num_hidden_layers"]):
            for kind, shape in kinds.items():
                key = (shape["order"], shape["cols"])
                members = groups.setdefault(key, [])
                self.where.append((key, len(members)))
                members.append(len(self.where) - 1)
        self.factors = len(self.where)
        self.groups = groups
        self.pool = [[data.kfac_factor(seed, i, v, self.where[i][0][0],
                                       f["tokens"], f["damping"], nat)
                      for i in range(self.factors)]
                     for v in range(self.versions)]
        jax.block_until_ready(self.pool)
        t = now()
        self.banks = {}
        for key, members in groups.items():
            pair = []
            for transpose in (False, True):
                bank = api.FactorBank(grid, key[0], capacity=len(members),
                                      precision=cfg["precision"],
                                      transpose=transpose)
                for i in members:
                    bank.admit(self.pool[0][i])
                pair.append(api.Solver.from_bank(bank))
            self.banks[key] = pair
        jax.block_until_ready([s.bank.stacks()
                               for pair in self.banks.values()
                               for s in pair])
        self.admit_s = now() - t
        rng = np.random.default_rng([seed, 1])
        self.B, self.idx = {}, {}
        for g, ((n, k), members) in enumerate(groups.items()):
            prog = self.banks[(n, k)][0].program_for(k)
            self.B[(n, k)] = data.rhs_stack(seed, len(members), n, k,
                                            prog.rhs_sharding, group=g)
            self.idx[(n, k)] = np.sort(rng.choice(
                k, min(k, mix["check_cols"]), replace=False))
        idx = [self.idx[key] for key in groups]
        self.take = jax.jit(lambda XY: [(X[:, :, i], Y[:, :, i])
                                        for (X, Y), i in zip(XY, idx)])
        self.step_cols = sum(shape["cols"] for shape in kinds.values()) \
            * cfg["num_hidden_layers"]
        s0 = next(iter(self.banks.values()))[0]
        self.plan = (f"method={s0.method} mesh=(1,1) precision="
                     f"{cfg['precision']} groups (order, cols, factors, "
                     f"n0): " + ", ".join(
                         f"({n}, {k}, {len(m)}, {self.banks[(n, k)][0].n0})"
                         for (n, k), m in groups.items()))
        self.programs = {
            "solve": "jit_" + s0.program_for(
                next(iter(groups))[1]).solve.__name__,
            "update": "jit_" + api.updater_for(
                s0.bank.update_spec(), s0.bank.cache).update.__name__}
        if (control or {}).get("solve") == "plain_high":
            self._plain_control()

    def solve(self):
        """Every group: X = L^-1 B, then Y = L^-T X; [(X, Y)]."""
        out = []
        for key, (fwd, bwd) in self.banks.items():
            X = fwd.solve(self.B[key], donate=False)
            out.append((X, bwd.solve(X, donate=False)))
        return out

    def refresh(self, i, version) -> list:
        """Factor i to ``version`` in both its banks; the seconds of
        each replace_factor call until the bank's stacks are ready."""
        key, slot = self.where[i]
        times = []
        for solver in self.banks[key]:
            t = now()
            solver.replace_factor(slot, self.pool[version][i])
            self.jax.block_until_ready(solver.bank.stacks())
            times.append(now() - t)
        return times

    def _plain_control(self):
        """The reference in the program's place, at Precision.HIGH: a
        plain blocked substitution, forward and transposed, over the
        natural factors the banks hold (the program's refresh still
        runs)."""
        jax, jnp = self.jax, self.jax.numpy
        self.cur = {key: jnp.stack([self.pool[0][i] for i in members])
                    for key, members in self.groups.items()}
        setter = jax.jit(lambda c, s, L: c.at[s].set(L), donate_argnums=0)
        plain = {key: (jax.jit(functools.partial(
            reference.plain_solve, block=key[0] // 8,
            dot=reference.dot_high)), jax.jit(functools.partial(
                reference.plain_solve_transposed, block=key[0] // 8,
                dot=reference.dot_high)))
            for key in self.groups}
        program_refresh = self.refresh

        def refresh(i, version):
            times = program_refresh(i, version)
            key, slot = self.where[i]
            self.cur[key] = setter(self.cur[key], slot,
                                   self.pool[version][i])
            return times

        def solve():
            out = []
            for key, (fwd, bwd) in plain.items():
                X = fwd(self.cur[key], self.B[key])
                out.append((X, bwd(self.cur[key], X)))
            return out
        self.refresh, self.solve = refresh, solve

    def warmup(self) -> None:
        self.refresh(0, 0)
        self.jax.block_until_ready(self.take(self.solve()))

    def window(self, seconds: float) -> dict:
        sample = load.Reservoir(self.mix["check_answers"], self.seed,
                                self.take)
        res = load.drive(self, self.mix, seconds, sample)
        res["sample"] = sample
        steps = res["steps"]
        res["counters"] = dict(cols=res["cols"], steps=steps)
        # per step two triangular solves of every factor's columns; the
        # factors read once in the window, each solve's B and X once
        fb, io = self.cfg["factor_bytes"], self.cfg["io_bytes"]
        W = Q = 0.0
        for (n, k), members in self.groups.items():
            m = len(members)
            W += steps * 2 * work.solve_flops(n, k, m)
            Q += work.solve_bytes(n, 0, fb, 0, m) \
                + steps * 2 * work.solve_bytes(n, k, 0, io, m)
        res["work"] = dict(W=W, Q=Q)
        return res

    def free(self) -> None:
        del self.banks
        self.solve = self.refresh = None
        self.__dict__.pop("cur", None)
        gc.collect()

    def answers(self, sample):
        """[(holds, [(X, Y) per group] on the host)], and B's sampled
        columns."""
        self.Bh = {key: np.asarray(B[:, :, self.idx[key]])
                   for key, B in self.B.items()}
        del self.B
        return [(holds, [(np.asarray(X), np.asarray(Y)) for X, Y in XY])
                for holds, XY in sample.items]

    def check(self, items) -> list:
        """Per factor and version: L X = B and L^T Y = X, every sampled
        step that held that version."""
        import concurrent.futures
        keys = list(self.groups)
        cases = [(i, v) for i in range(self.factors)
                 for v in range(self.versions)
                 if any(holds[i] == v for holds, _ in items)]

        def errors(L, xs, bs):
            good = [(x, b) for x, b in zip(xs, bs) if x.shape == b.shape]
            errs = [float("inf")] * (len(xs) - len(good))
            if good:
                edges = np.cumsum([0] + [x.shape[1] for x, _ in good])
                errs += reference.backward_errors(
                    [(0, L)], np.concatenate([x for x, _ in good], 1),
                    np.concatenate([b for _, b in good], 1), edges,
                    workers=1)
            return errs

        def one(case):
            i, v = case
            key, slot = self.where[i]
            g = keys.index(key)
            held = [XY[g] for holds, XY in items if holds[i] == v]
            xs = [X[slot] for X, _ in held]
            ys = [Y[slot] for _, Y in held]
            L = np.asarray(self.pool[v][i])
            return errors(L, xs, [self.Bh[key][slot]] * len(xs)) \
                + errors(L.T, ys, xs)
        with concurrent.futures.ThreadPoolExecutor(8) as ex:
            return [e for errs in ex.map(one, cases) for e in errs]


SYSTEMS = {"dense": Dense, "kfac": Kfac}


# ------------------------------ the run ------------------------------

def percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def run(args, *, control=None) -> dict:
    """One run of one cell; returns the result line's object.  Raises
    Refused where no run can be made."""
    spec = load_cell(args.workload, args.cpu_rehearsal)
    cfg, mix, cell = spec["cfg"], spec["mix"], spec["cell"]
    try:
        import jax
        from repro import api
        from repro.core import session
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        raise Refused(f"cannot import the program ({e})")
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.cpu_rehearsal:
        raise Refused(f"no TPU (JAX reports {platform}); refusing to run")
    if len(devices) < cell["chips"]:
        raise Refused(f"cell needs {cell['chips']} chips, JAX sees "
                      f"{len(devices)}")
    kind = devices[0].device_kind
    peak = work.peaks(kind) if platform == "tpu" else None
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"device: {platform} {kind} x{len(devices)}; cell "
        f"{args.workload} on {cell['chips']} chip(s); compile cache {cache}")
    used = devices[:cell["chips"]]

    system = SYSTEMS[cfg["system"]](api, jax, cfg, mix, args.seed, used,
                                    control)
    log(f"plan: {system.plan}")
    log(f"admission: {system.admit_s:.6f} s (factor on the device -> "
        f"resident stacks ready)")
    system.warmup()
    traces0 = sum(session.TRACE_COUNTS.values())
    setup_s = now() - T_START
    tdir = None
    with load.counting_compiles() as seen:
        if args.trace:
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            # host spans and device ops; no Python function tracing,
            # which would slow the host path the window measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.window"):
                res = system.window(min(args.seconds, mix["trace_seconds"]))
            jax.profiler.stop_trace()
        else:
            res = system.window(args.seconds)
    retraces = sum(session.TRACE_COUNTS.values()) - traces0
    log(f"window: {res['t_end'] - res['t0']:.6f} s, {res['attempted']} "
        f"requests, {res['failed']} missing; inside it {seen['compiles']} "
        f"compiles, {seen['traces']} traces, {retraces} program retraces")
    if res.get("lags"):
        lags = res["lags"]
        log(f"client lag (answer back -> next request sent): mean "
            f"{1e3 * float(np.mean(lags)):.6f} ms, p95 "
            f"{1e3 * percentile(lags, 95):.6f} ms, max "
            f"{1e3 * max(lags):.6f} ms over {len(lags)} resubmits")
    mem = 0
    for d in used:
        st = d.memory_stats() or {}
        mem = max(mem, int(st.get("peak_bytes_in_use", 0)))
    log(f"process peak HBM: {mem} bytes ({mem / 2 ** 30:.6f} GiB) on the "
        f"fullest chip")

    out_metrics = {}
    breakdown = None
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": mem}
    if args.trace:
        tr = trace.load(tdir)
        if args.keep_trace:
            shutil.copytree(tdir, args.keep_trace, dirs_exist_ok=True)
            rows = trace.describe(tdir)
            with open(os.path.join(args.keep_trace, "describe.json"),
                      "w") as f:
                json.dump(rows, f, indent=1)
        shutil.rmtree(tdir, ignore_errors=True)
        win = trace.window(tr)
        busy = trace.busy(tr, win)
        device["busy_s"] = float(np.mean(list(busy.values()))) / 1e9 \
            if busy else 0.0
        device["window_s"] = (win[1] - win[0]) / 1e9
        breakdown = {"device_ops": trace.top_ops(tr, win),
                     "idle_gaps": trace.idle_gaps(tr, win)}
        ctx = types.SimpleNamespace(
            trace=tr, window=win, programs=system.programs,
            solve_work=res["work"], peak=peak, chips=cell["chips"],
            counters=res["counters"], admit_s=system.admit_s, notes=[])
        for m in spec["per_layer"]:
            mod = importlib.import_module(f"bench.metrics.{m['name']}")
            if platform != "tpu" and m["source"] == "device_trace":
                continue            # a rehearsal reads no device metric
            v = mod.read(ctx)
            if v is not None:
                out_metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
        for note in ctx.notes:
            log(note)
    else:
        span = res["t_end"] - res["t0"]
        e2e = {"rhs_cols_per_s": res["cols"] / span if span > 0 else 0.0,
               "setup_s": setup_s}
        if res.get("latencies"):
            e2e["req_p95_ms"] = 1e3 * percentile(res["latencies"], 95)
        if res.get("refresh_s"):
            e2e["refresh_p95_ms"] = 1e3 * percentile(res["refresh_s"], 95)
        for m in spec["end_to_end"]:
            if m["name"] in e2e:
                out_metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                          "unit": m["unit"]}

    # the check: answers to the host, the program's state freed, then
    # the float64 reference
    t = now()
    answers = system.answers(res.pop("sample"))
    system.free()
    errs = system.check(answers)
    limit = cfg["limits"]["max_backward_error"]
    worst = max(errs) if errs else float("inf")
    wrong = sum(e > limit for e in errs)
    log(f"reference: {len(errs)} answers compared in {now() - t:.6f} s")
    checked = {
        "max_backward_error": {"value": worst, "limit": limit},
        "answers_missing": {"value": res["failed"], "limit": 0},
        "answers_compared": {"value": len(errs), "limit": 1},
    }
    correct = bool(errs) and worst <= limit and res["failed"] == 0
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"] + wrong, "metrics": out_metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checked"] = checked
    del system, answers
    gc.collect()                    # the next run in this process
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on whatever JAX finds, at each file's "
                         "rehearsal sizes (no device numbers)")
    ap.add_argument("--keep-trace", default=None,
                    help="with --trace 1: copy the trace and a listing "
                         "of its planes and lines to this directory")
    args = ap.parse_args(argv)
    try:
        out = run(args)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in out["checked"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
