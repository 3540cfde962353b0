"""On-device iterative refinement for the cyclic solve pipeline
(DESIGN.md Sec. 7).

Classic mixed-precision refinement (Wilkinson; Carson/Higham for the
low-precision-factorization revival): solve in low precision, then
repeat

    r   = B - op(A) X          (residual precision)
    d   = solve(op(A), r)      (low-precision sweep, reused)
    X  += d

Each pass contracts the error by ~(eps_compute * kappa), so a couple of
passes recover residual-precision accuracy from a bf16 sweep whenever
the factor is not close to singular at bf16.

Everything here is designed to live INSIDE the one compiled program of
``repro.core.session``:

* the loop is a fixed-trip Python loop, unrolled at trace time — no
  host-side convergence test, hence zero steady-state host transfers
  and zero retraces (the session invariants extend to refined solves);
* the residual reuses the RESIDENT cyclic factor: for a factor
  distributed as ``L_cyc = Pr · op(A)_eff · Pc^T`` (rows stride-p1
  cyclic, cols stride-p1·p2 cyclic, reversal/transpose folded in —
  repro.core.grid), the operator applies to a natural-layout X as

      op(A) X  =  unpermute_rows( L_cyc @ permute_rows(X, col-map) )

  i.e. two O(nk) on-device gathers around one product — no second
  layout, no host permutation, and the SAME expression serves all four
  (lower, transpose) operator variants because the reduction identities
  are already folded into ``L_cyc``'s gathers;
* on one device (p1 * p2 == 1) both cyclic maps are the identity (or a
  reversal that turns an upper factor lower), so ``L_cyc`` is lower
  triangular and the product is ROW-BLOCKED: each block of rows
  multiplies only the columns up to its diagonal tile, about
  (T+1)/(2T) of the dense GEMM's multiply-adds for T blocks, at the
  same precision.  On a larger mesh the global ``L_cyc`` is a grid of
  interleaved triangular quadrants that GSPMD shards; slicing it
  globally would reshard, so there the product stays one dense GEMM.
"""

from __future__ import annotations

import jax.lax
import jax.numpy as jnp

from repro.core import grid as gridlib
from repro.core.precision import PrecisionPolicy, gemm_precision


# The one-chip residual's row blocks: ``_ROW_BLOCKS`` blocks of a whole
# number of ``_ALIGN`` rows (the MXU's tile), the last one ragged; below
# ``_MIN_BLOCK_ROWS`` rows a block the dense product is used instead.
# 16 blocks beat 4 and 8 on a v5e at n = 32768 for k = 4096 and k = 256:
# the blocked dots run at the dense dot's rate, so fewer multiply-adds
# win (PERF.md, section 6).
_ROW_BLOCKS = 16
_ALIGN = 128
_MIN_BLOCK_ROWS = 512


def residual_row_bounds(n: int) -> tuple[int, ...]:
    """End row (exclusive) of each row block of the one-chip residual
    product of order ``n``; ``(n,)`` is the dense product."""
    rows = -(-n // _ROW_BLOCKS)
    rows = -(-rows // _ALIGN) * _ALIGN
    if rows < _MIN_BLOCK_ROWS:
        return (n,)
    return tuple(range(rows, n, rows)) + (n,)


def _row_bounds(n: int, p1: int, p2: int) -> tuple[int, ...]:
    return residual_row_bounds(n) if p1 * p2 == 1 else (n,)


def residual_tile_share(n: int, p1: int, p2: int) -> float:
    """The share of the dense (n, n) residual GEMM's multiply-adds that
    :func:`apply_cyclic_operator` does on a (p1, p2) mesh: (T+1)/(2T)
    for T equal row blocks, 1.0 for the dense product."""
    bounds = _row_bounds(n, p1, p2)
    starts = (0,) + bounds[:-1]
    return sum((e - s) * e for s, e in zip(starts, bounds)) / (n * n)


def lower_product(L, X, bounds, *, precision, preferred_element_type):
    """``L @ X`` for a lower-triangular ``L`` (n, n), or a stack of them
    (M, n, n) with X (M, n, k): the rows split at ``bounds`` (end row of
    each block, last == n), block I multiplying ``L[rows_I, :end_I]``
    by ``X[:end_I]``.  No tile above the diagonal tiles is read, so the
    result is the dense product's in exact arithmetic (only the
    summation order differs) PROVIDED L is zero above its diagonal —
    which the solver assumes of every lower factor anyway: admission
    applies no ``tril``.  ``bounds == (n,)`` is the one dense GEMM."""
    kw = dict(precision=precision,
              preferred_element_type=preferred_element_type)

    def dot(a, b):
        if a.ndim == 2:
            return jax.lax.dot(a, b, **kw)
        return jax.lax.dot_general(
            a, b, dimension_numbers=(((2,), (1,)), ((0,), (0,))), **kw)
    if len(bounds) == 1:
        return dot(L, X)
    starts = (0,) + tuple(bounds[:-1])
    return jnp.concatenate([dot(L[..., s:e, :e], X[..., :e, :])
                            for s, e in zip(starts, bounds)], axis=-2)


def apply_cyclic_operator(L_cyc, X, *, p1: int, p2: int, reverse: bool,
                          accum_dtype=None):
    """Compute ``op(A) @ X`` (natural layout in and out) from the
    resident cyclic factor.

    ``L_cyc`` is the distribution-time gather of op(A) with row map
    ``G_r`` (stride p1, reversal ``reverse``) and column map ``G_c``
    (stride p1*p2, same reversal): ``L_cyc = G_r op(A) G_c^T``.  Then

        op(A) X = G_r^{-1} ( L_cyc @ G_c X )

    — one gather of X's rows by the factor's COLUMN map, the product
    against the resident factor, and the inverse gather by the factor's
    ROW map.  The transpose flag needs no case here: it was applied to
    the matrix before distribution, so it is part of op(A) already.

    On a (1, 1) mesh ``L_cyc`` is lower triangular and the product is
    :func:`lower_product` over :func:`residual_row_bounds`; on a larger
    mesh it is one dense GEMM (module docstring).

    Accepts stacked operands too — L_cyc (M, n, n) with X (M, n, k) —
    in which case the gathers permute the trailing row axis and the
    product is batched: a factor bank's whole refinement residual is
    the same few ops (DESIGN.md Sec. 9).
    """
    Xg = gridlib.cyclic_rows_device(X, p1 * p2, reverse=reverse)
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None else X.dtype
    Xg = Xg.astype(L_cyc.dtype)
    Y = lower_product(L_cyc, Xg, _row_bounds(L_cyc.shape[-1], p1, p2),
                      precision=gemm_precision(L_cyc, Xg),
                      preferred_element_type=acc)
    return gridlib.cyclic_rows_device(Y, p1, inverse=True, reverse=reverse)


def refined_solve(base_solve, L_lo, L_hi, B, *, policy: PrecisionPolicy,
                  p1: int, p2: int, reverse: bool):
    """The refined solve body (traced inside the session's program).

    ``base_solve(L_cyc, B) -> X`` is the compute-precision sweep
    (natural layout in/out, the existing permute -> shard_map sweep ->
    unpermute body).  ``L_lo``/``L_hi`` are the resident cyclic factor
    at storage and residual precision (``L_hi`` may be None when the
    policy does not refine).  Returns X at ``policy.io_dtype``.
    """
    io = policy.io_dtype
    B = jnp.asarray(B, io)
    X = base_solve(L_lo, B.astype(policy.compute_dtype))
    if not policy.refines:
        return X.astype(io)
    res = policy.residual_dtype
    X = X.astype(res)
    for _ in range(policy.refine_steps):        # unrolled: one program
        r = B - apply_cyclic_operator(L_hi, X, p1=p1, p2=p2,
                                      reverse=reverse, accum_dtype=res)
        d = base_solve(L_lo, r.astype(policy.compute_dtype))
        X = X + d.astype(res)
    return X
