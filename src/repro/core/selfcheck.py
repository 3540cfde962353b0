"""Multi-device self-checks for the distributed core algorithms.

Run as a subprocess with forced host devices (tests do this):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.core.selfcheck [what]

Exits nonzero on the first failure.  Kept as a module (not a test) so it
can run under a different jax device configuration than the main pytest
process (which must see exactly 1 device).
"""

from __future__ import annotations

import os
import sys

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

from repro import compat
import numpy as np


def _random_tril(seed, n, dtype=np.float64):
    rng = np.random.default_rng(seed)
    L = np.tril(rng.standard_normal((n, n)))
    return (L + n * np.eye(n)).astype(dtype)


def check_it_inv_trsm() -> int:
    from repro.core import grid as gridlib
    from repro.core import inv_trsm

    jax.config.update("jax_enable_x64", True)
    fails = 0
    cases = [
        # (p1, p2, n, k, n0, mode)
        (2, 2, 32, 8, 4, None),       # m=8 == p -> alltoall
        (2, 2, 32, 8, 8, None),       # m=4 < p -> allgather fallback
        (2, 2, 64, 16, 8, "alltoall"),
        (2, 2, 64, 16, 8, "allgather"),
        (2, 1, 32, 6, 8, None),
        (1, 2, 32, 8, 16, None),
        (1, 8, 64, 8, 8, None),
        (2, 2, 64, 64, 16, None),
        (1, 1, 16, 4, 4, None),
    ]
    for (p1, p2, n, k, n0, mode) in cases:
        grid = gridlib.make_trsm_mesh(p1, p2)
        L = _random_tril(n, n)
        B = np.random.default_rng(k).standard_normal((n, k))
        X = inv_trsm.solve(jnp.asarray(L), jnp.asarray(B), grid, n0,
                           mode=mode)
        ref = np.asarray(
            jax.scipy.linalg.solve_triangular(jnp.asarray(L),
                                              jnp.asarray(B), lower=True))
        err = np.abs(X - ref).max()
        ok = err < 1e-8
        print(f"it_inv_trsm p1={p1} p2={p2} n={n} k={k} n0={n0} "
              f"mode={mode}: err={err:.2e} {'OK' if ok else 'FAIL'}")
        if not ok:
            fails += 1
    return fails


def check_collective_order() -> int:
    """Verify the flattening order assumptions for tuple-axis collectives."""
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.asarray(jax.devices())[:8].reshape(2, 2, 2)
    mesh = Mesh(devs, ("x", "y", "z"))
    fails = 0

    def body(a):
        xi = jax.lax.axis_index("x")
        yi = jax.lax.axis_index("y")
        zi = jax.lax.axis_index("z")
        fid = (xi * 2 + yi) * 2 + zi
        g = jax.lax.all_gather(jnp.array([fid]), ("x", "y", "z"),
                               axis=0, tiled=True)
        return g[None]

    f = compat.shard_map(body, mesh=mesh, in_specs=P("x", ("z", "y")),
                      out_specs=P(("x", "y", "z")))
    out = np.asarray(jax.jit(f)(jnp.zeros((2, 4))))
    expect = np.arange(8)
    if not np.array_equal(out[0], expect):
        print("all_gather tuple-axis order MISMATCH:", out[0])
        fails += 1
    else:
        print("all_gather tuple-axis order OK (x-major row-major)")

    def body2(a):
        xi = jax.lax.axis_index("x")
        yi = jax.lax.axis_index("y")
        zi = jax.lax.axis_index("z")
        fid = (xi * 2 + yi) * 2 + zi
        # each device holds 8 items tagged (src, slot); after a tiled
        # all_to_all device d should hold items (src=0..7, slot=d)
        items = fid * 8 + jnp.arange(8)
        r = jax.lax.all_to_all(items, ("x", "y", "z"), split_axis=0,
                               concat_axis=0, tiled=True)
        return r[None]

    f2 = compat.shard_map(body2, mesh=mesh, in_specs=P("x", ("z", "y")),
                       out_specs=P(("x", "y", "z")))
    out2 = np.asarray(jax.jit(f2)(jnp.zeros((2, 4))))
    # device d (flattened x-major) holds rows d of the output spec
    for d in range(8):
        expect = np.arange(8) * 8 + d
        if not np.array_equal(out2[d], expect):
            print(f"all_to_all order MISMATCH on dev {d}:", out2[d])
            fails += 1
            break
    else:
        print("all_to_all tuple-axis order OK")
    return fails


def check_mm3d() -> int:
    from repro.core import grid as gridlib
    from repro.core import mm3d

    jax.config.update("jax_enable_x64", True)
    fails = 0
    for (p1, p2, m, n, k) in [(2, 2, 16, 16, 8), (2, 1, 8, 8, 4),
                              (1, 2, 8, 8, 8), (1, 8, 16, 16, 16),
                              (2, 2, 32, 16, 8), (1, 1, 8, 8, 4),
                              (2, 2, 16, 16, 64)]:
        grid = gridlib.make_trsm_mesh(p1, p2)
        rng = np.random.default_rng(m * n)
        L = rng.standard_normal((m, n))
        X = rng.standard_normal((n, k))
        B = mm3d.matmul(L, X, grid)
        err = np.abs(B - L @ X).max()
        ok = err < 1e-10
        print(f"mm3d p1={p1} p2={p2} m={m} n={n} k={k}: err={err:.2e} "
              f"{'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    return fails


def check_tri_inv() -> int:
    from repro.core import grid as gridlib
    from repro.core import tri_inv

    jax.config.update("jax_enable_x64", True)
    fails = 0
    for (p1, p2, n, s0, mode) in [(2, 2, 64, None, None),
                                  (2, 2, 64, 8, "alltoall"),
                                  (2, 2, 32, 8, "allgather"),
                                  (1, 2, 32, None, None),
                                  (2, 1, 32, None, None),
                                  (1, 8, 64, None, None),
                                  (1, 1, 16, None, None)]:
        grid = gridlib.make_trsm_mesh(p1, p2)
        L = _random_tril(n, n)
        Li = tri_inv.invert(L, grid, s0=s0, mode=mode)
        err = np.abs(Li @ L - np.eye(n)).max()
        ok = err < 1e-9 and np.allclose(np.triu(Li, 1), 0)
        print(f"tri_inv p1={p1} p2={p2} n={n} s0={s0} mode={mode}: "
              f"err={err:.2e} {'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    return fails


def check_rec_trsm() -> int:
    from repro.core import grid as gridlib
    from repro.core import rec_trsm

    jax.config.update("jax_enable_x64", True)
    fails = 0
    for (p1, p2, n, k, n0) in [(2, 2, 64, 16, 16), (2, 2, 64, 16, None),
                               (2, 1, 32, 8, 8), (1, 2, 32, 4, None),
                               (1, 8, 64, 16, None), (1, 1, 16, 4, 4),
                               (2, 2, 32, 32, 8)]:
        grid = gridlib.make_trsm_mesh(p1, p2)
        L = _random_tril(n, n)
        B = np.random.default_rng(1).standard_normal((n, k))
        X = rec_trsm.solve(L, B, grid, n0)
        err = np.abs(X - np.linalg.solve(L, B)).max()
        ok = err < 1e-9
        print(f"rec_trsm p1={p1} p2={p2} n={n} k={k} n0={n0}: "
              f"err={err:.2e} {'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    return fails


def check_cholesky() -> int:
    from repro.core import grid as gridlib
    from repro.core import cholesky

    jax.config.update("jax_enable_x64", True)
    rng = np.random.default_rng(0)
    fails = 0
    for (p1, p2, n, n0) in [(2, 2, 32, 8), (2, 1, 32, 16), (1, 2, 16, 8),
                            (2, 2, 64, 16)]:
        grid = gridlib.make_trsm_mesh(p1, p2)
        M = rng.standard_normal((n, n))
        A = M @ M.T + n * np.eye(n)
        L = cholesky.cholesky(A, grid, n0)
        err = np.abs(L @ L.T - A).max()
        ok = err < 1e-8 and np.allclose(np.triu(L, 1), 0)
        print(f"cholesky p1={p1} p2={p2} n={n} n0={n0}: err={err:.2e} "
              f"{'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    # transpose helper
    for (p1, p2, mr, nc) in [(2, 2, 16, 32), (2, 1, 16, 8), (1, 2, 8, 16)]:
        grid = gridlib.make_trsm_mesh(p1, p2)
        A = rng.standard_normal((mr, nc))
        Ac = gridlib.to_cyclic_matrix(A, p1, p1 * p2)
        T = gridlib.from_cyclic_matrix(
            np.asarray(cholesky.transpose_fn(grid, mr, nc)(Ac)), p1, p1 * p2)
        ok = np.array_equal(T, A.T)
        print(f"transpose p1={p1} p2={p2} {mr}x{nc}: "
              f"{'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    return fails


def check_doubling_mode() -> int:
    from repro.core import grid as gridlib
    from repro.core import inv_trsm

    jax.config.update("jax_enable_x64", True)
    fails = 0
    for (p1, p2, n, k, n0) in [(2, 2, 64, 16, 32), (2, 2, 64, 16, 16),
                               (1, 8, 64, 8, 32)]:
        grid = gridlib.make_trsm_mesh(p1, p2)
        L = _random_tril(n, n)
        B = np.random.default_rng(2).standard_normal((n, k))
        X = inv_trsm.solve(L, B, grid, n0, mode="doubling")
        err = np.abs(X - np.linalg.solve(L, B)).max()
        ok = err < 1e-9
        print(f"doubling p1={p1} p2={p2} n={n} n0={n0}: err={err:.2e} "
              f"{'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    return fails


def check_lu() -> int:
    from repro.core import grid as gridlib
    from repro.core import lu

    jax.config.update("jax_enable_x64", True)
    rng = np.random.default_rng(0)
    fails = 0
    for (p1, p2, n, n0) in [(2, 2, 32, 8), (2, 1, 32, 16), (1, 2, 16, 8),
                            (2, 2, 64, 16)]:
        grid = gridlib.make_trsm_mesh(p1, p2)
        A = rng.standard_normal((n, n)) + n * np.eye(n)
        L, U = lu.lu(A, grid, n0)
        err = np.abs(L @ U - A).max()
        ok = (err < 1e-8 and np.allclose(np.triu(L, 1), 0)
              and np.allclose(np.tril(U, -1), 0)
              and np.allclose(np.diag(L), 1))
        print(f"lu p1={p1} p2={p2} n={n} n0={n0}: err={err:.2e} "
              f"{'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    return fails


def check_session() -> int:
    """Device-resident pipeline: lower/upper/transposed solves via the
    compiled-solver cache and a width-1 Solver, on multi-device
    grids."""
    from repro import core
    from repro.core import grid as gridlib, session

    jax.config.update("jax_enable_x64", True)
    fails = 0
    rng = np.random.default_rng(3)
    for (p1, p2, n, k, n0, method) in [(2, 2, 64, 16, 16, "inv"),
                                       (2, 1, 32, 8, 8, "inv"),
                                       (1, 2, 32, 8, 16, "rec"),
                                       (2, 2, 64, 16, 16, "rec")]:
        grid = gridlib.make_trsm_mesh(p1, p2)
        L = _random_tril(n, n)
        B = rng.standard_normal((n, k))
        for lower, transpose in [(True, False), (False, False),
                                 (True, True), (False, True)]:
            A = L if lower else L.T
            op = A.T if transpose else A
            X = core.trsm(A, B, grid, method=method, n0=n0, lower=lower,
                          transpose=transpose)
            err = np.abs(op @ np.asarray(X) - B).max()
            ok = err < 1e-8
            print(f"session {method} p1={p1} p2={p2} n={n} "
                  f"lower={lower} T={transpose}: err={err:.2e} "
                  f"{'OK' if ok else 'FAIL'}")
            fails += 0 if ok else 1
        # steady state: resident factor, no retrace across repeated solves
        sess = core.Solver.from_factor(L, grid, method=method, n0=n0)
        sess.warmup(k)
        key = sess.program_for(k).key
        before = session.TRACE_COUNTS[key]
        Bs = [sess.place_rhs(rng.standard_normal((n, k)))
              for _ in range(3)]
        with jax.transfer_guard("disallow"):
            # donate=False: B is re-read below to verify the residual
            outs = [sess.solve(b, donate=False) for b in Bs]
        err = max(np.abs(L @ np.asarray(x[0]) - np.asarray(b[0])).max()
                  for b, x in zip(Bs, outs))
        steady = session.TRACE_COUNTS[key] == before
        ok = err < 1e-8 and steady
        print(f"session steady p1={p1} p2={p2} {method}: err={err:.2e} "
              f"retraces={'0' if steady else 'NONZERO'} "
              f"{'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    # mixed precision on a multi-device grid: bf16 sweep + on-device
    # refinement serves fp32-grade answers with the same steady state
    for (p1, p2, method) in [(2, 2, "inv"), (2, 2, "rec")]:
        grid = gridlib.make_trsm_mesh(p1, p2)
        n, k, n0 = 64, 16, 16
        L = _random_tril(5, n, np.float32)
        sess = core.Solver.from_factor(L, grid, method=method, n0=n0,
                                       precision="bf16_refine")
        sess.warmup(k)
        key = sess.program_for(k).key
        before = session.TRACE_COUNTS[key]
        B = sess.place_rhs(rng.standard_normal((n, k)).astype(np.float32))
        with jax.transfer_guard("disallow"):
            X = sess.solve(B, donate=False)
        rel = (np.linalg.norm(L.astype(np.float64)
                              @ np.asarray(X[0], np.float64)
                              - np.asarray(B[0]))
               / np.linalg.norm(np.asarray(B[0])))
        steady = session.TRACE_COUNTS[key] == before
        ok = rel < 1e-5 and steady and X.dtype == jnp.float32
        print(f"session bf16_refine p1={p1} p2={p2} {method}: "
              f"relres={rel:.2e} retraces={'0' if steady else 'NONZERO'} "
              f"{'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    return fails


def check_bank() -> int:
    """Multi-factor batched serving on multi-device grids: stacked
    admission + cyclic ingestion, vmap/scan mapped programs, mixed
    precision, and the banked steady state (DESIGN.md Sec. 9)."""
    from repro import core
    from repro.core import cholesky, grid as gridlib, session
    from repro.core.bank import FactorBank

    jax.config.update("jax_enable_x64", True)
    fails = 0
    rng = np.random.default_rng(9)
    M, n, k = 3, 64, 16
    for (p1, p2, method, map_mode, precision) in [
            (2, 2, "inv", "vmap", None),
            (2, 2, "inv", "scan", None),
            (2, 1, "rec", "vmap", None),
            (2, 2, "inv", "vmap", "bf16_refine")]:
        grid = gridlib.make_trsm_mesh(p1, p2)
        dt = np.float32 if precision else np.float64
        Ls = np.stack([_random_tril(10 + i, n, dt) for i in range(M)])
        bank = FactorBank(grid, n, method=method,
                          n0=None if method == "inv" else 16,
                          dtype=None if precision else dt,
                          precision=precision, map_mode=map_mode)
        bank.admit_stack(Ls[:2])
        bank.admit(Ls[2])
        sess = core.Solver.from_bank(bank)
        key = sess.program_for(k).key
        before = session.TRACE_COUNTS[key]
        sess.warmup(k)
        Bs = [sess.place_rhs(rng.standard_normal((M, n, k)).astype(dt))
              for _ in range(3)]
        with jax.transfer_guard("disallow"):
            outs = [sess.solve(b, donate=False) for b in Bs]
        rel = max(np.linalg.norm(Ls[i].astype(np.float64)
                                 @ np.asarray(x[i], np.float64)
                                 - np.asarray(b[i]))
                  / np.linalg.norm(np.asarray(b[i]))
                  for b, x in zip(Bs, outs) for i in range(M))
        steady = session.TRACE_COUNTS[key] == before + 1
        ok = rel < (1e-5 if precision else 1e-10) and steady
        print(f"bank {method} p1={p1} p2={p2} {map_mode} "
              f"{precision or 'uniform'} n0={bank.n0}: relres={rel:.2e} "
              f"retraces={'0' if steady else 'NONZERO'} "
              f"{'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    # cyclic ingestion from a grid-resident factorization
    grid = gridlib.make_trsm_mesh(2, 2)
    L0 = _random_tril(20, n)
    A = L0 @ L0.T
    bank = FactorBank(grid, n, dtype=np.float64)
    bank.admit_cyclic(cholesky.cholesky_cyclic(A, grid))
    sess = core.Solver.from_bank(bank)
    B = rng.standard_normal((1, n, k))
    X = np.asarray(sess.solve(sess.place_rhs(B))[0], np.float64)
    Lnat = np.asarray(cholesky.cholesky(A, grid), np.float64)
    rel = np.linalg.norm(Lnat @ X - B[0]) / np.linalg.norm(B[0])
    ok = rel < 1e-10
    print(f"bank cyclic-ingest p1=2 p2=2: relres={rel:.2e} "
          f"{'OK' if ok else 'FAIL'}")
    fails += 0 if ok else 1
    return fails


def check_overlap() -> int:
    """Bit-identity of the software-pipelined sweep (DESIGN.md
    Sec. 16): overlap on/off runs the SAME collectives on the same
    operands in a different issue order, so the solve output must be
    byte-equal — per method, per grid shape (degenerate p2=1 and
    p1=1 included), and per structure."""
    from repro import api
    from repro.core import grid as gridlib
    from repro.core.structure import FactorStructure

    jax.config.update("jax_enable_x64", True)
    fails = 0
    cases = [
        # (p1, p2, method, n, k, n0, structure)
        (2, 2, "inv", 64, 8, 16, None),
        (2, 1, "inv", 64, 8, 16, None),      # degenerate z axis
        (1, 2, "inv", 64, 8, 16, None),      # degenerate x/y axes
        (2, 2, "rec", 64, 8, 16, None),
        (2, 2, "inv", 64, 8, 16, FactorStructure.banded(16)),
    ]
    for (p1, p2, method, n, k, n0, st) in cases:
        grid = gridlib.make_trsm_mesh(p1, p2)
        L = _random_tril(n, n)
        if st is not None and st.kind == "banded":
            ii = np.arange(n)
            L *= np.abs(ii[:, None] - ii[None, :]) < st.bandwidth
        B = np.random.default_rng(k).standard_normal((n, k))
        outs = {}
        for ov in ("on", "off"):
            solver = api.Solver.from_factor(
                L, grid, method=method, n0=n0, structure=st, overlap=ov)
            outs[ov] = np.asarray(solver.solve(B, donate=False))
        bit = outs["on"].tobytes() == outs["off"].tobytes()
        err = np.abs(L @ outs["on"] - B).max()
        ok = bit and err < 1e-7
        tag = st.kind if st is not None else "dense"
        print(f"overlap p1={p1} p2={p2} {method} {tag}: "
              f"bit-identical={bit} err={err:.2e} "
              f"{'OK' if ok else 'FAIL'}")
        if not ok:
            fails += 1
    return fails


# Phase 1's data movement spelled as reshapes and transposes (a
# size-p1/p2 axis minor-most, which the TPU's tiling pads): the
# reference that the gathers of repro.core.grid must match bit for bit.
def _legacy_assemble_blocks(Dg, p1, p2):
    p, m, a, b = Dg.shape
    R = Dg.reshape(p1, p1, p2, m, a, b)            # (x, y, z, i, l, c)
    R = jnp.transpose(R, (3, 4, 0, 5, 2, 1))       # (i, l, x, c, z, y)
    return R.reshape(m, a * p1, b * p2 * p1)


def _legacy_piece_for(binv, row_off, col_off, p1):
    m, n0, _ = binv.shape
    a = n0 // p1
    R = jnp.moveaxis(binv.reshape(m, a, p1, a, p1), (2, 4), (0, 1))
    R = jax.lax.dynamic_index_in_dim(R, row_off, axis=0, keepdims=False)
    return jax.lax.dynamic_index_in_dim(R, col_off, axis=0, keepdims=False)


def _legacy_pieces_all_dests(binv, p1, p2):
    mb, n0, _ = binv.shape
    a = n0 // p1
    R = binv.reshape(mb, a, p1, a, p1)             # (i, l, roff, c, coff)
    R = jnp.transpose(R, (4, 2, 0, 1, 3))
    R = jnp.broadcast_to(R[:, :, None], (p1, p1, p2, mb, a, a))
    return R.reshape(p1 * p1 * p2, mb, a, a)


def _legacy_cyclic_piece(blocks, x, y, z, p1, p2):
    m, s, _ = blocks.shape
    a, b = s // p1, s // (p1 * p2)
    R = blocks.reshape(m, a, p1, b, p2, p1)        # [i, l, x, c', z, y]
    R = jnp.moveaxis(R, (2, 4, 5), (0, 1, 2))      # [x, z, y, i, l, c']
    R = jax.lax.dynamic_index_in_dim(R, x, axis=0, keepdims=False)
    R = jax.lax.dynamic_index_in_dim(R, z, axis=0, keepdims=False)
    return jax.lax.dynamic_index_in_dim(R, y, axis=0, keepdims=False)


def _legacy_pieces_for_all(blocks, p1, p2):
    m, s, _ = blocks.shape
    a, b = s // p1, s // (p1 * p2)
    R = blocks.reshape(m, a, p1, b, p2, p1)        # [i, l, x, c', z, y]
    R = jnp.transpose(R, (2, 5, 4, 0, 1, 3))       # [x, y, z, i, l, c']
    return R.reshape(p1 * p1 * p2, m, a, b)


def _legacy_phase1():
    """Context: phase 1 built from the legacy data movement."""
    import contextlib
    from repro.core import inv_trsm, tri_inv

    @contextlib.contextmanager
    def swapped():
        swaps = [(inv_trsm, "_piece_for", _legacy_piece_for),
                 (inv_trsm, "_pieces_all_dests", _legacy_pieces_all_dests),
                 (tri_inv, "_assemble_blocks", _legacy_assemble_blocks),
                 (tri_inv, "_cyclic_piece", _legacy_cyclic_piece),
                 (tri_inv, "_pieces_for_all", _legacy_pieces_for_all)]
        saved = [(mod, name, getattr(mod, name)) for mod, name, _ in swaps]
        for mod, name, fn in swaps:
            setattr(mod, name, fn)
        try:
            yield
        finally:
            for mod, name, fn in saved:
                setattr(mod, name, fn)
    return swapped()


def check_phase1_z() -> int:
    """The phase-1 program runs with the vma check off because its
    out_specs claim Dt is replicated over z (see
    ``inv_trsm.it_inv_phase1_sharded``).  Assert that claim: on every
    mesh a four-chip host allows, and on (2, 2), for every phase-1
    mode, the z-replica shards of Dt are BIT-equal, Dt is bit-equal to
    the Dt of the legacy data movement (``_legacy_phase1``), and the
    assembled Dt is the cyclic storage of the inverted diagonal
    blocks."""
    from jax.sharding import NamedSharding
    from repro.core import grid as gridlib
    from repro.core import inv_trsm, tri_inv

    jax.config.update("jax_enable_x64", True)
    fails = 0
    n = 64
    L = _random_tril(4, n)
    for (p1, p2) in [(1, 1), (1, 2), (1, 4), (2, 1), (2, 2)]:
        grid = gridlib.make_trsm_mesh(p1, p2)
        Lc = jax.device_put(gridlib.to_cyclic_matrix(L, p1, p1 * p2),
                            NamedSharding(grid.mesh, grid.spec_L()))
        for n0 in (8, 16, 32):
            if n0 % (p1 * p2):
                continue
            m = n // n0
            s0 = min(tri_inv.pick_s0(n, p1, p2), n0)
            modes = ["allgather"]
            if m % grid.p == 0:
                modes.append("alltoall")
            if s0 % (p1 * p2) == 0 and (n0 // s0) & (n0 // s0 - 1) == 0:
                modes.append("doubling")
            want = np.stack([
                gridlib.to_cyclic_matrix(np.linalg.inv(
                    L[i * n0:(i + 1) * n0, i * n0:(i + 1) * n0]), p1, p1)
                for i in range(m)])
            for mode in modes:
                Dt = jax.jit(inv_trsm.it_inv_phase1_sharded(
                    grid, n, n0, mode=mode))(Lc)
                with _legacy_phase1():
                    old = jax.jit(inv_trsm.it_inv_phase1_sharded(
                        grid, n, n0, mode=mode))(Lc)
                replicas: dict = {}
                for sh in Dt.addressable_shards:
                    replicas.setdefault(str(sh.index), []).append(
                        np.asarray(sh.data).tobytes())
                bit = all(len(set(r)) == 1 for r in replicas.values())
                same = np.asarray(Dt).tobytes() == np.asarray(old).tobytes()
                err = np.abs(np.asarray(Dt) - want).max()
                ok = bit and same and err < 1e-10
                print(f"phase1_z p1={p1} p2={p2} n0={n0} mode={mode}: "
                      f"z-replicas bit-equal={bit} legacy bit-equal={same} "
                      f"err={err:.2e} {'OK' if ok else 'FAIL'}")
                fails += 0 if ok else 1
    return fails


def _dominant_tril(seed, n):
    """HPL-MxP's recipe in small: strictly lower part uniform in
    [-1/2, 1/2), n on the diagonal (float32)."""
    rng = np.random.default_rng(seed)
    L = np.tril(rng.uniform(-0.5, 0.5, (n, n)), -1) + n * np.eye(n)
    return L.astype(np.float32)


def _backward_error(L, X, B) -> float:
    """Normwise float64 backward error of X to L X = B."""
    L, X, B = (np.asarray(a, np.float64) for a in (L, X, B))
    return float(np.abs(L @ X - B).sum(1).max()
                 / (np.abs(L).sum(1).max() * np.abs(X).sum(1).max()
                    + np.abs(B).sum(1).max()))


def check_cyclic_serve() -> int:
    """The four-chip HPL-MxP deployment in small: a seeded diagonally
    dominant factor of order 512 made in cyclic storage on mesh (2, 1),
    admitted by ``FactorBank.admit_cyclic`` at the plan the front door
    picks, served by ``Solver`` and by ``AsyncSolveServer`` under
    ``bf16_refine`` and ``fp32``.  Each answer is held to the float64
    triangular solve (LAPACK) and to a backward-error limit that the
    same factor served under ``bf16`` fails; the collective counters
    of both stats are positive."""
    import scipy.linalg
    from jax.sharding import NamedSharding
    from repro import api
    from repro.core import grid as gridlib

    fails = 0
    n, k, p1, p2 = 512, 64, 2, 1
    limit, near = 2e-5, 1e-4          # backward error; rel. to float64
    L = _dominant_tril(15, n)
    B = np.random.default_rng(16).standard_normal((n, k)).astype(np.float32)
    ref = scipy.linalg.solve_triangular(L.astype(np.float64),
                                        B.astype(np.float64), lower=True)
    grid = api.make_trsm_mesh(p1, p2)
    sh = NamedSharding(grid.mesh, grid.spec_L())
    for precision in ("bf16_refine", "fp32", "bf16"):
        bank = api.FactorBank(grid, n, precision=precision)
        bank.admit_cyclic(jax.device_put(
            gridlib.to_cyclic_matrix(L, p1, p1 * p2), sh))
        solver = api.Solver.from_bank(bank)
        X = np.asarray(solver.solve(jnp.asarray(B), donate=False))
        with api.AsyncSolveServer(solver, panel_k=k) as server:
            futs = [server.submit(B[:, j]) for j in range(4)]
            Xa = np.stack([np.asarray(f.result(timeout=300)).reshape(n)
                           for f in futs], axis=1)
        errs = [_backward_error(L, X, B),
                _backward_error(L, Xa, B[:, :4])]
        rel = max(np.abs(X - ref).max(), np.abs(Xa - ref[:, :4]).max()) \
            / np.abs(ref).max()
        sound = max(errs) <= limit and rel <= near
        counted = [(st["collectives_per_solve"],
                    st["collective_words_per_col"])
                   for st in (solver.stats(), server.stats())]
        ok = (not sound if precision == "bf16" else sound) \
            and all(c > 0 and w > 0 for c, w in counted)
        print(f"cyclic_serve p1={p1} p2={p2} n={n} n0={bank.n0} "
              f"phase1={bank._phase1_mode} {precision}: backward error "
              f"Solver {errs[0]:.2e}, AsyncSolveServer {errs[1]:.2e} "
              f"(limit {limit:.0e}), max rel. to float64 {rel:.2e}; "
              f"collectives a solve, words a column (Solver, server) "
              f"{counted} {'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    return fails


def _dense_residual(L_cyc, X, *, p1, p2, reverse, accum_dtype=None):
    """The refinement residual as one dense GEMM (the reference the
    meshes past one device must still compile to)."""
    from repro.core import grid as gridlib
    from repro.core.precision import gemm_precision
    Xg = gridlib.cyclic_rows_device(X, p1 * p2, reverse=reverse)
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None else X.dtype
    Xg = Xg.astype(L_cyc.dtype)
    Y = jax.lax.dot_general(
        L_cyc, Xg, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=gemm_precision(L_cyc, Xg), preferred_element_type=acc)
    return gridlib.cyclic_rows_device(Y, p1, inverse=True, reverse=reverse)


def check_residual_dense() -> int:
    """Past one device the refinement residual stays one dense GEMM: a
    served bf16_refine program of order 8192 (sixteen row blocks on one
    device) lowers to exactly the program built with the dense
    residual, and ``residual_tile_share`` reads 1.0 in both stats."""
    from repro import api
    from repro.core import refine, session

    fails = 0
    n, k = 8192, 8
    L = _dominant_tril(21, n)
    B = np.random.default_rng(22).standard_normal((n, k)).astype(
        np.float32)
    for p1, p2 in [(2, 1), (1, 2)]:
        grid = api.make_trsm_mesh(p1, p2)
        bank = api.FactorBank(grid, n, precision="bf16_refine")
        bank.admit(jnp.asarray(L))
        solver = api.Solver.from_bank(bank)
        with api.AsyncSolveServer(solver, panel_k=k) as server:
            server.submit(B[:, 0]).result(timeout=300)
        prog = solver.program_for(k)
        Bp = solver.place_rhs(B)
        text = prog.solve.lower(bank.stacks(), Bp).as_text()
        orig = refine.apply_cyclic_operator
        refine.apply_cyclic_operator = _dense_residual    # traced in lower
        try:
            ref = session._build_solver(prog.key).solve.lower(
                bank.stacks(), Bp).as_text()
        finally:
            refine.apply_cyclic_operator = orig
        same = ref == text
        shares = [st["residual_tile_share"]
                  for st in (solver.stats(), server.stats())]
        blocked = len(refine.residual_row_bounds(n)) > 1
        ok = same and blocked and shares == [1.0, 1.0]
        print(f"residual_dense p1={p1} p2={p2} n={n}: lowers to the dense"
              f" residual's program {same}; blocked on one device "
              f"{blocked}; residual_tile_share (Solver, server) {shares} "
              f"{'OK' if ok else 'FAIL'}")
        fails += 0 if ok else 1
    return fails


CHECKS = {
    "order": check_collective_order,
    "phase1_z": check_phase1_z,
    "it_inv_trsm": check_it_inv_trsm,
    "mm3d": check_mm3d,
    "tri_inv": check_tri_inv,
    "rec_trsm": check_rec_trsm,
    "cholesky": check_cholesky,
    "doubling": check_doubling_mode,
    "lu": check_lu,
    "session": check_session,
    "bank": check_bank,
    "overlap": check_overlap,
    "cyclic_serve": check_cyclic_serve,
    "residual_dense": check_residual_dense,
}


def main(argv):
    what = argv[1] if len(argv) > 1 else None
    names = [what] if what else list(CHECKS)
    fails = 0
    for name in names:
        fails += CHECKS[name]()
    print(f"selfcheck: {fails} failures")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
