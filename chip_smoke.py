#!/usr/bin/env python3
"""Chip smoke test: the served It-Inv-TRSM path, end to end, on a TPU.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four chips: the distributed solve
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal --n 512 \
        --bank-n 256                  # the same phases on the CPU

Everything runs in this one process through the public front door
(``repro.api``); nothing falls back to the CPU or to interpret mode
unless ``--cpu-rehearsal`` says so.

* Phase A — one dense lower-triangular factor of order ``--n``
  (default 32768), generated in float32 from ``--seed``, admitted with
  ``Solver.from_factor`` and served by ``SolveServer`` (panel_k=128,
  16 requests of widths 1-128) under the ``fp32`` and ``bf16_refine``
  presets.  The ``bf16`` preset runs too, as the negative control: it
  must FAIL the tolerance, which shows the check would catch an f32
  GEMM that ran as a single bf16 pass.
* Phase B — a capacity-8 ``FactorBank`` at order ``--bank-n`` (4096),
  fp32: admit, replace, evict, re-admit, then 16 requests through an
  ``AsyncSolveServer``, every future's ``.result()`` taken.
* Phase C — the three Pallas kernels compiled for the chip at
  n0 in {128, 256}, against ``repro.kernels.ref``.
* ``--chips 4`` — only phase A's serving at order 16384, fp32, on the
  meshes (p1, p2) = (1, 4) and (2, 1), each against the float64
  reference and against a 1x1 solve on the first device.

Every solution is checked on the host in float64 by its normwise
backward error ||LX - B||_inf / (||L||_inf ||X||_inf + ||B||_inf).
The last line of standard output is one JSON object naming the device;
any failed check exits non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

# Backward-error bound for the f32-accurate presets.  float32 solves
# land near 1e-7 and the bf16 preset near 1e-3 on these factors, so a
# GEMM that silently ran as one bf16 pass cannot pass it.
TOL = 2e-5
PANEL_K = 128
REQUESTS = 16


def log(*a):
    print(*a, flush=True)


def make_factor(n: int, seed: int) -> np.ndarray:
    """Dense lower-triangular (n, n) float32 factor, diagonally dominant
    (standard-normal strictly lower part, n on the diagonal), built in
    row chunks so no n x n float64 array ever exists."""
    rng = np.random.default_rng(seed)
    L = np.empty((n, n), np.float32)
    chunk = 2048
    for r0 in range(0, n, chunk):
        r1 = min(n, r0 + chunk)
        blk = rng.standard_normal((r1 - r0, n), dtype=np.float32)
        rows = np.arange(r0, r1)[:, None]
        blk[np.arange(n)[None, :] > rows] = 0.0
        blk[np.arange(r1 - r0), np.arange(r0, r1)] += n
        L[r0:r1] = blk
    return L


def make_requests(n: int, seed: int, count: int = REQUESTS):
    rng = np.random.default_rng(seed)
    widths = rng.integers(1, PANEL_K + 1, count)
    return [rng.standard_normal((n, int(w)), dtype=np.float32)
            for w in widths]


def backward_errors(L, Xs, Bs, chunk: int = 1024) -> list:
    """float64 normwise backward error of every (X, B) pair against the
    float32 factor L (exact in float64), reading L in row chunks."""
    X = np.concatenate([np.asarray(x, np.float64) for x in Xs], axis=1)
    B = np.concatenate([np.asarray(b, np.float64) for b in Bs], axis=1)
    if not np.isfinite(X).all():
        return [float("inf")] * len(Xs)
    edges = np.cumsum([0] + [x.shape[1] for x in Xs])
    res = np.zeros(len(Xs))
    lnorm = 0.0
    for r0 in range(0, L.shape[0], chunk):
        Lc = L[r0:r0 + chunk].astype(np.float64)
        lnorm = max(lnorm, np.abs(Lc).sum(1).max())
        R = np.abs(Lc @ X - B[r0:r0 + chunk])
        for j in range(len(Xs)):
            res[j] = max(res[j], R[:, edges[j]:edges[j + 1]].sum(1).max())
    out = []
    for j in range(len(Xs)):
        cols = slice(edges[j], edges[j + 1])
        xn = np.abs(X[:, cols]).sum(1).max()
        bn = np.abs(B[:, cols]).sum(1).max()
        out.append(float(res[j] / (lnorm * xn + bn)))
    return out


class Checks:
    def __init__(self):
        self.failed = []

    def expect(self, ok: bool, what: str) -> None:
        log(f"  {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)


def serve_factor(api, jax, L, grid, precision, Bs):
    """Admit L through the front door, warm the server, serve Bs.
    Returns (solutions on the host, plan line, seconds dict)."""
    t0 = time.perf_counter()
    solver = api.Solver.from_factor(L, grid, precision=precision,
                                    k_hint=PANEL_K)
    jax.block_until_ready(solver.bank.stacks())
    t1 = time.perf_counter()
    server = api.SolveServer(solver, PANEL_K).warmup()
    jax.block_until_ready(solver.bank.stacks())
    t2 = time.perf_counter()
    for b in Bs:
        server.submit(b)
    outs = server.drain()[0]
    Xs = [np.asarray(x) for x in outs]
    t3 = time.perf_counter()
    plan = (f"method={solver.method} n0={solver.n0} "
            f"phase1={solver.bank._phase1_mode} p1={grid.p1} "
            f"p2={grid.p2} panels={server.panels_solved}")
    stats = grid.mesh.devices.flat[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        plan += (f"; process peak HBM so far "
                 f"{stats['peak_bytes_in_use'] / 2 ** 30:.3f} GiB")
    secs = dict(admission=t1 - t0, compile=t2 - t1, serve=t3 - t2)
    del server, solver
    gc.collect()
    return Xs, plan, secs


def phase_a(api, jax, ck, n, seed):
    log(f"phase A: one factor, n={n}, panel_k={PANEL_K}, "
        f"{REQUESTS} requests")
    L = make_factor(n, seed)
    Bs = make_requests(n, seed + 1)
    grid = api.make_trsm_mesh(1, 1)
    errs = {}
    for precision in ("fp32", "bf16_refine", "bf16"):
        Xs, plan, secs = serve_factor(api, jax, L, grid, precision, Bs)
        eta = backward_errors(L, Xs, Bs)
        errs[precision] = max(eta)
        log(f"  {precision}: plan {plan}")
        log(f"  {precision}: admission {secs['admission']:.3f} s, "
            f"compile (warmup) {secs['compile']:.3f} s, "
            f"serve {secs['serve']:.3f} s (wall clock, not a benchmark)")
        log(f"  {precision}: backward errors "
            + " ".join(f"{e:.3e}" for e in eta))
        shapes = all(x.shape == b.shape for x, b in zip(Xs, Bs))
        ck.expect(shapes, f"A {precision}: every X has its request's shape")
        if precision != "bf16":
            ck.expect(errs[precision] < TOL,
                      f"A {precision}: max backward error "
                      f"{errs[precision]:.3e} < {TOL:g}")
    ck.expect(errs["bf16"] > 10 * TOL,
              f"A bf16 control: max backward error {errs['bf16']:.3e} "
              f"> 10 x {TOL:g} (the tolerance catches one-pass bf16)")


def phase_b(api, jax, ck, n, seed):
    log(f"phase B: live bank, capacity 8, n={n}, fp32, AsyncSolveServer")
    grid = api.make_trsm_mesh(1, 1)
    bank = api.FactorBank(grid, n, capacity=8, precision="fp32")
    solver = api.Solver.from_bank(bank)
    server = api.AsyncSolveServer(solver, panel_k=PANEL_K,
                                  queue_depth=REQUESTS).warmup()
    resident = {}                          # slot -> the factor it holds
    for i in range(8):
        L = make_factor(n, seed + 10 + i)
        resident[bank.admit(L)] = L
    new3 = make_factor(n, seed + 30)
    solver.replace_factor(3, new3)
    resident[3] = new3
    solver.evict_factor(5)
    new5 = make_factor(n, seed + 31)
    slot = solver.admit_factor(new5)
    ck.expect(slot == 5, f"B re-admit fills the evicted slot ({slot})")
    resident[slot] = new5
    Bs = make_requests(n, seed + 2)
    t0 = time.perf_counter()
    with server:
        futs = [server.submit(b, i % 8) for i, b in enumerate(Bs)]
        Xs = [np.asarray(f.result(timeout=600)) for f in futs]
    log(f"  served {len(Xs)} requests in {time.perf_counter() - t0:.3f} s "
        f"(wall clock, not a benchmark); waves={server.waves}")
    worst = 0.0
    for i, (x, b) in enumerate(zip(Xs, Bs)):
        eta = backward_errors(resident[i % 8], [x], [b])[0]
        worst = max(worst, eta)
    log(f"  backward errors: max {worst:.3e}")
    ck.expect(worst < TOL, f"B fp32 bank: max backward error "
              f"{worst:.3e} < {TOL:g}")
    del server, solver, bank
    gc.collect()


def phase_c(jax, ck, seed, interpret):
    import jax.numpy as jnp
    from repro.kernels import ref, trmm, tri_inv_block, trsm_block
    log(f"phase C: Pallas kernels (interpret={interpret})")
    rng = np.random.default_rng(seed)

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.abs(a - b).max() / np.abs(b).max())

    for n0 in (128, 256):
        Ls = np.tril(rng.standard_normal((4, n0, n0))) \
            + n0 * np.eye(n0)
        Ls = jnp.asarray(Ls, jnp.float32)
        L = jnp.asarray(np.tril(rng.standard_normal((4 * n0, 4 * n0))),
                        jnp.float32)
        X = jnp.asarray(rng.standard_normal((4 * n0, PANEL_K)),
                        jnp.float32)
        B = jnp.asarray(rng.standard_normal((4, n0, PANEL_K)),
                        jnp.float32)
        got = {
            "trmm": (jax.jit(lambda a, x: trmm.trmm(
                a, x, bt=n0, interpret=interpret))(L, X),
                ref.trmm_ref(L, X)),
            "tri_inv_blocks": (jax.jit(lambda a: tri_inv_block
                               .tri_inv_blocks(a, interpret=interpret))(
                Ls), ref.tri_inv_blocks_ref(Ls)),
            "trsm_substitution": (jax.jit(lambda a, b: trsm_block
                                  .trsm_substitution(
                                      a, b, interpret=interpret))(Ls, B),
                                  jax.vmap(ref.trsm_ref)(Ls, B)),
        }
        for name, (out, want) in got.items():
            e = rel(out, want)
            ck.expect(e < 1e-4, f"C {name} n0={n0}: relative error "
                      f"{e:.3e} < 1e-4")


def phase_mesh(api, jax, ck, n, seed):
    log(f"phase 4-chip: n={n}, fp32, meshes (1,4) and (2,1)")
    L = make_factor(n, seed)
    Bs = make_requests(n, seed + 1)
    devs = jax.devices()
    X1, plan, secs = serve_factor(api, jax, L,
                                  api.make_trsm_mesh(1, 1, [devs[0]]),
                                  "fp32", Bs)
    log(f"  1x1 on {devs[0]}: plan {plan}; admission "
        f"{secs['admission']:.3f} s, compile {secs['compile']:.3f} s")
    eta = max(backward_errors(L, X1, Bs))
    ck.expect(eta < TOL, f"1x1: max backward error {eta:.3e} < {TOL:g}")
    for p1, p2 in ((1, 4), (2, 1)):
        grid = api.make_trsm_mesh(p1, p2)
        axes = {name: [[d.id for d in np.asarray(grid.mesh.devices)
                        .take(i, axis=ax).reshape(-1)]
                       for i in range(grid.mesh.devices.shape[ax])]
                for ax, name in enumerate(grid.mesh.axis_names)}
        log(f"  mesh ({p1},{p2}) devices by axis index: {axes}")
        Xs, plan, secs = serve_factor(api, jax, L, grid, "fp32", Bs)
        log(f"  ({p1},{p2}): plan {plan}; admission "
            f"{secs['admission']:.3f} s, compile {secs['compile']:.3f} s, "
            f"serve {secs['serve']:.3f} s (wall clock, not a benchmark)")
        eta = backward_errors(L, Xs, Bs)
        log(f"  ({p1},{p2}): backward errors "
            + " ".join(f"{e:.3e}" for e in eta))
        ck.expect(max(eta) < TOL, f"({p1},{p2}): max backward error "
                  f"{max(eta):.3e} < {TOL:g}")
        diff = max(float(np.abs(x - y).max() / np.abs(y).max())
                   for x, y in zip(Xs, X1))
        ck.expect(diff < 1e-4, f"({p1},{p2}) vs 1x1: max relative "
                  f"difference {diff:.3e} < 1e-4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=None,
                    help="phase-A order (default 32768; 16384 with "
                         "--chips 4)")
    ap.add_argument("--bank-n", type=int, default=4096,
                    help="phase-B order")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="allow a non-TPU backend (kernels in interpret "
                         "mode); for rehearsing at small n")
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    try:
        import jax
        from repro import api
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e})",
              file=sys.stderr)
        return 2
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no JAX backend ({e})", file=sys.stderr)
        return 1
    platform = devs[0].platform
    if platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: no TPU (JAX reports {platform}); refusing "
              f"to run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 1
    log(f"device: {platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {use_compile_cache()}")

    ck = Checks()
    if args.chips == 4:
        phase_mesh(api, jax, ck, args.n or 16384, args.seed)
    else:
        phase_a(api, jax, ck, args.n or 32768, args.seed)
        phase_b(api, jax, ck, args.bank_n, args.seed)
        phase_c(jax, ck, args.seed, interpret=platform != "tpu")
    if ck.failed:
        print(f"chip_smoke: {len(ck.failed)} check(s) failed: "
              + "; ".join(ck.failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
