"""The float64 reference at a small order: it passes the program's
fp32 solve of a factor and fails the bf16-preset solve of the same
factor; the HIGH product it uses for controls is the three-pass one."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import data, reference

N, K = 256, 8
LIMIT = 2e-5          # the dense configurations' limit


def _solve(precision):
    from repro import api
    L = data.dense_factor(3, N, None)
    B = np.asarray(data.rhs(3, 0, N, K))
    solver = api.Solver.from_factor(L, api.make_trsm_mesh(1, 1),
                                    precision=precision)
    X = np.asarray(solver.solve(jnp.asarray(B)))
    rows = np.asarray(data.dense_factor_rows(3, N, 0, N))
    return reference.backward_errors([(0, rows[:100]), (100, rows[100:])],
                                     X, B, [0, K // 2, K])


def test_reference_passes_fp32_and_fails_bf16():
    assert max(_solve("fp32")) < LIMIT
    assert min(_solve("bf16")) > 10 * LIMIT


def test_rows_match_the_whole_factor():
    whole = np.asarray(data.dense_factor(9, N, None))
    rows = np.asarray(data.dense_factor_rows(9, N, 64, 32))
    np.testing.assert_array_equal(rows, whole[64:96])
    assert np.all(np.triu(whole, 1) == 0) and np.all(np.diag(whole) == N)


def test_cyclic_layout_holds_the_same_entries():
    from repro.core import grid
    nat = np.asarray(data.dense_factor(9, N, None))
    cyc = np.asarray(data.dense_factor(9, N, None, 2, 4))
    np.testing.assert_array_equal(cyc, grid.to_cyclic_matrix(nat, 2, 4))


def test_non_finite_answer_reads_inf():
    L = np.tril(np.ones((4, 4))) + 3 * np.eye(4)
    X = np.ones((4, 2))
    X[1, 1] = np.nan
    errs = reference.backward_errors([(0, L)], X, L @ np.ones((4, 2)),
                                     [0, 1, 2])
    assert errs[0] < 1e-15 and errs[1] == float("inf")


def test_dot_high_is_three_passes():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((32, 512)).astype(np.float32)
    b = rng.standard_normal((512, 16)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    scale = np.abs(a).astype(np.float64) @ np.abs(b)
    err = np.max(np.abs(np.asarray(reference.dot_high(a, b)) - exact)
                 / scale)
    # between one bf16 pass (~2**-9) and float32 (~2**-24)
    assert 2.0 ** -24 < err < 2.0 ** -12


def test_plain_solve_solves():
    L = np.asarray(data.dense_factor(4, 128, None))
    B = np.asarray(data.rhs(4, 0, 128, 4))
    X = jax.jit(lambda l, b: reference.plain_solve(
        l, b, block=32, dot=jnp.matmul))(L, B)
    err = reference.backward_errors([(0, L)], np.asarray(X), B, [0, 4])[0]
    assert err < 1e-6
