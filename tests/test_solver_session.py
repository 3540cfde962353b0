"""Device-resident solve pipeline: on-device cyclic permutations vs the
NumPy reference, the compiled-solver cache, and TrsmSession's
zero-transfer / zero-retrace steady state (single-device grid; the
multi-device versions run in repro.core.selfcheck session)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import grid as gridlib, session
from repro.core.grid import (cyclic_matrix_device, cyclic_rows_device,
                             from_cyclic_matrix, from_cyclic_rows,
                             to_cyclic_matrix, to_cyclic_rows)


@pytest.fixture(autouse=True, scope="module")
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="module")
def grid():
    return gridlib.make_trsm_mesh(1, 1)


def _mats(n=64, k=8, seed=0):
    rng = np.random.default_rng(seed)
    L = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    B = rng.standard_normal((n, k))
    return L, B


# ---------------- device permutations == NumPy reference ----------------

@pytest.mark.parametrize("n,p", [(16, 1), (16, 2), (64, 4), (60, 3)])
def test_cyclic_rows_device_roundtrip(n, p):
    a = np.random.default_rng(n * p).standard_normal((n, 5))
    dev = np.asarray(cyclic_rows_device(jnp.asarray(a), p))
    np.testing.assert_array_equal(dev, to_cyclic_rows(a, p))
    back = np.asarray(cyclic_rows_device(jnp.asarray(dev), p,
                                         inverse=True))
    np.testing.assert_array_equal(back, a)
    np.testing.assert_array_equal(back, from_cyclic_rows(dev, p))


@pytest.mark.parametrize("n,p", [(16, 2), (64, 4)])
def test_cyclic_rows_device_reversal(n, p):
    """reverse=True folds the upper/transpose reversal identity into the
    same single gather: forward == to_cyclic(a[::-1])."""
    a = np.random.default_rng(1).standard_normal((n, 3))
    fwd = np.asarray(cyclic_rows_device(jnp.asarray(a), p, reverse=True))
    np.testing.assert_array_equal(fwd, to_cyclic_rows(a[::-1], p))
    back = np.asarray(cyclic_rows_device(jnp.asarray(fwd), p,
                                         inverse=True, reverse=True))
    np.testing.assert_array_equal(back, a)


@pytest.mark.parametrize("pr,pc", [(1, 1), (2, 4), (4, 2)])
def test_cyclic_matrix_device_matches_numpy(pr, pc):
    A = np.random.default_rng(2).standard_normal((32, 32))
    dev = np.asarray(cyclic_matrix_device(jnp.asarray(A), pr, pc))
    np.testing.assert_array_equal(dev, to_cyclic_matrix(A, pr, pc))
    back = np.asarray(cyclic_matrix_device(jnp.asarray(dev), pr, pc,
                                           inverse=True))
    np.testing.assert_array_equal(back, from_cyclic_matrix(
        to_cyclic_matrix(A, pr, pc), pr, pc))
    np.testing.assert_array_equal(back, A)


def test_cyclic_matrix_device_reversal_transpose():
    """The operator reductions: JAJ (reversal) and A^T, as one gather."""
    A = np.random.default_rng(3).standard_normal((16, 16))
    pr, pc = 2, 4
    rev = np.asarray(cyclic_matrix_device(
        jnp.asarray(A), pr, pc, reverse_rows=True, reverse_cols=True))
    np.testing.assert_array_equal(rev, to_cyclic_matrix(A[::-1, ::-1],
                                                        pr, pc))
    tr = np.asarray(cyclic_matrix_device(jnp.asarray(A), pr, pc,
                                         transpose=True))
    np.testing.assert_array_equal(tr, to_cyclic_matrix(A.T, pr, pc))


# ------------------- solve correctness via the pipeline -------------------

@pytest.mark.parametrize("method", ["inv", "rec"])
@pytest.mark.parametrize("lower,transpose", [(True, False), (False, False),
                                             (True, True), (False, True)])
def test_trsm_variants_device_pipeline(grid, method, lower, transpose):
    L, B = _mats()
    A = L if lower else L.T
    op = A.T if transpose else A
    X = core.trsm(A, B, grid, method=method, n0=16, lower=lower,
                  transpose=transpose)
    np.testing.assert_allclose(op @ np.asarray(X), B, atol=1e-3)


# ------------------------------ the cache ------------------------------

def test_solver_cache_reuses_compiled_program(grid):
    L, B = _mats()
    session.default_cache().clear()
    session.TRACE_COUNTS.clear()
    X1 = core.trsm(L, B, grid, method="inv", n0=16)
    X2 = core.trsm(L, B, grid, method="inv", n0=16)
    np.testing.assert_allclose(np.asarray(X1), np.asarray(X2))
    st = session.default_cache().stats()
    assert st["misses"] == 1 and st["hits"] == 1, st
    # one cached program, traced exactly once across both calls
    (key,) = list(session.TRACE_COUNTS)
    assert session.TRACE_COUNTS[key] == 1
    # a different shape is a different program
    core.trsm(L, B[:, :4], grid, method="inv", n0=16)
    assert session.default_cache().stats()["misses"] == 2


def test_solver_cache_lru_eviction(grid):
    cache = session.CompiledSolverCache(maxsize=2)
    L, B = _mats(n=32, k=4)
    for k in (1, 2, 4):
        session.get_solver(grid, n=32, k=k, dtype=np.float64,
                           method="inv", n0=8, cache=cache)
    assert len(cache) == 2 and cache.evictions == 1


# The session invariants — zero steady-state host transfers, zero
# retraces — must hold for EVERY precision preset: the refinement loop
# is unrolled inside the one compiled program, so a refined solve is
# still a single executable with no host round-trips.
@pytest.mark.parametrize("precision,in_dt,rtol", [
    (None, np.float64, 1e-10),          # legacy uniform-dtype policy
    ("fp32", np.float32, 1e-5),
    ("bf16", np.float32, 5e-2),
    ("bf16_refine", np.float32, 1e-5),
    ("fp64_refine", np.float64, 1e-11),
])
def test_session_steady_state_no_transfers_no_retraces(grid, precision,
                                                       in_dt, rtol):
    L, _ = _mats(n=64, k=8)
    L = L.astype(in_dt)
    sess = core.TrsmSession(L, grid, method="inv", n0=16,
                            precision=precision)
    sess.warmup(8)
    key = sess.program_for(8).key
    traces_after_warmup = session.TRACE_COUNTS[key]
    assert traces_after_warmup == 1     # one trace per cached program
    rng = np.random.default_rng(7)
    Bs = [sess.place_rhs(rng.standard_normal((64, 8)).astype(in_dt))
          for _ in range(4)]
    refs = [np.asarray(b) for b in Bs]
    with jax.transfer_guard("disallow"):
        outs = [sess.solve(b) for b in Bs]      # donate=True: B consumed
    assert session.TRACE_COUNTS[key] == traces_after_warmup
    for b, x in zip(refs, outs):
        assert x.dtype == sess.dtype
        x64 = np.asarray(x, np.float64)
        rel = (np.linalg.norm(L.astype(np.float64) @ x64 - b)
               / np.linalg.norm(b))
        assert rel < rtol, (precision, rel)
    assert sess.solves_served == 5              # warmup + 4


def test_multifactor_cache_sharing_no_baked_constants(grid):
    """Two same-shape sessions with DIFFERENT factor values must share
    one compiled program — the factor is a runtime operand, never a
    constant folded into the executable — and the same must hold for
    same-width factor banks (the batched program is keyed on the bank
    width, not on the factors)."""
    from repro.core.bank import BatchedTrsmSession, FactorBank
    session.default_cache().clear()
    session.TRACE_COUNTS.clear()
    L1, B = _mats(seed=1)
    L2, _ = _mats(seed=2)

    s1 = core.TrsmSession(L1, grid, method="inv", n0=16)
    s2 = core.TrsmSession(L2, grid, method="inv", n0=16)
    X1 = s1.solve(s1.place_rhs(B))
    X2 = s2.solve(s2.place_rhs(B))
    assert s1.program_for(8).key == s2.program_for(8).key
    (key,) = list(session.TRACE_COUNTS)
    assert session.TRACE_COUNTS[key] == 1          # one trace, two sessions
    st = session.default_cache().stats()
    assert st["misses"] == 1 and st["hits"] >= 1, st
    # different factors -> different (correct) answers: nothing baked in
    np.testing.assert_allclose(L1 @ np.asarray(X1), B, atol=1e-8)
    np.testing.assert_allclose(L2 @ np.asarray(X2), B, atol=1e-8)
    assert not np.allclose(np.asarray(X1), np.asarray(X2))

    # the bank: same width + config -> one batched program, two banks
    session.TRACE_COUNTS.clear()
    Ls_a = np.stack([L1, L2])
    Ls_b = np.stack([L2, L1])
    banks = []
    for Ls in (Ls_a, Ls_b):
        bank = FactorBank(grid, 64, n0=16, dtype=np.float64)
        bank.admit_stack(Ls)
        banks.append(BatchedTrsmSession(bank))
    Bs = np.stack([B, B])
    Xa = banks[0].solve(banks[0].place_rhs(Bs))
    Xb = banks[1].solve(banks[1].place_rhs(Bs))
    bkey = banks[0].program_for(8).key
    assert bkey == banks[1].program_for(8).key and bkey != key
    assert session.TRACE_COUNTS[bkey] == 1         # one trace, two banks
    for Ls, X in ((Ls_a, Xa), (Ls_b, Xb)):
        for i in range(2):
            np.testing.assert_allclose(Ls[i] @ np.asarray(X[i]), B,
                                       atol=1e-8)
    assert not np.allclose(np.asarray(Xa), np.asarray(Xb))


def test_session_rejects_bad_rhs(grid):
    L, _ = _mats(n=32, k=4)
    sess = core.TrsmSession(L, grid, method="inv", n0=8)
    with pytest.raises(ValueError):
        sess.solve(jnp.zeros((16, 4)))
    with pytest.raises(ValueError):
        core.TrsmSession(np.zeros((8, 4)), grid)


# -------------------------- request batching --------------------------

def test_trsm_request_server_packs_and_answers():
    from repro.train import serve_step as ss
    n = 64
    rng = np.random.default_rng(5)
    L = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    server = ss.make_trsm_server(L, panel_k=4, n0=16)
    reqs = [rng.standard_normal((n, w)) for w in (1, 3, 2, 4, 1)]
    for r in reqs:
        server.submit(r)
    outs = server.drain()
    assert server.pending() == 0
    assert [o.shape[1] for o in outs] == [1, 3, 2, 4, 1]
    for r, x in zip(reqs, outs):
        np.testing.assert_allclose(L @ np.asarray(x), r, atol=1e-8)
    with pytest.raises(ValueError):
        server.submit(rng.standard_normal((n, 9)))   # wider than panel


def test_trsm_request_server_first_fit_no_head_of_line_underfill():
    """A wide head-of-line request must not strand narrow requests into
    underfilled panels: widths (3, 4, 1) at panel_k=4 pack as [3+1],
    [4] — two panels, not three — and drain still returns solutions in
    submit order."""
    from repro.train import serve_step as ss
    n = 64
    rng = np.random.default_rng(6)
    L = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    server = ss.make_trsm_server(L, panel_k=4, n0=16)
    reqs = [rng.standard_normal((n, w)) for w in (3, 4, 1)]
    for r in reqs:
        server.submit(r)
    outs = server.drain()
    assert server.panels_solved == 2, server.panels_solved
    assert [o.shape[1] for o in outs] == [3, 4, 1]   # submit order
    for r, x in zip(reqs, outs):
        np.testing.assert_allclose(L @ np.asarray(x), r, atol=1e-8)


# ----------------------- degenerate kernel blocks -----------------------

def test_block_inv_kernel_rejects_degenerate_blocks():
    from repro.kernels import ops
    with pytest.raises(ValueError, match="degenerate"):
        ops.block_inv_kernel(jnp.zeros((4, 0, 0)))
    with pytest.raises(ValueError, match="degenerate"):
        ops.block_inv_kernel(jnp.zeros((0, 4, 4)))
    with pytest.raises(ValueError, match="square"):
        ops.block_inv_kernel(jnp.zeros((2, 4, 8)))
    with pytest.raises(ValueError, match="stack"):
        ops.block_inv_kernel(jnp.zeros((4, 4)))
    with pytest.raises(ValueError, match="power of two"):
        ops.block_inv_kernel(jnp.ones((2, 3, 3)))
    # n0=1 is a power of two (the kernel's reciprocal level alone), and
    # valid blocks still invert
    out = ops.block_inv_kernel(jnp.ones((3, 1, 1)))
    np.testing.assert_allclose(np.asarray(out), np.ones((3, 1, 1)))
