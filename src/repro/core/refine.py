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

  i.e. two O(nk) on-device gathers around one GEMM — no second layout,
  no host permutation, and the SAME expression serves all four
  (lower, transpose) operator variants because the reduction identities
  are already folded into ``L_cyc``'s gathers.
"""

from __future__ import annotations

import jax.lax
import jax.numpy as jnp

from repro.core import grid as gridlib
from repro.core.precision import PrecisionPolicy, gemm_precision


def apply_cyclic_operator(L_cyc, X, *, p1: int, p2: int, reverse: bool,
                          accum_dtype=None):
    """Compute ``op(A) @ X`` (natural layout in and out) from the
    resident cyclic factor.

    ``L_cyc`` is the distribution-time gather of op(A) with row map
    ``G_r`` (stride p1, reversal ``reverse``) and column map ``G_c``
    (stride p1*p2, same reversal): ``L_cyc = G_r op(A) G_c^T``.  Then

        op(A) X = G_r^{-1} ( L_cyc @ G_c X )

    — one gather of X's rows by the factor's COLUMN map, the GEMM
    against the resident factor, and the inverse gather by the factor's
    ROW map.  The transpose flag needs no case here: it was applied to
    the matrix before distribution, so it is part of op(A) already.

    Accepts stacked operands too — L_cyc (M, n, n) with X (M, n, k) —
    in which case the gathers permute the trailing row axis and the
    GEMM is one batched contraction: a factor bank's whole refinement
    residual is three ops (DESIGN.md Sec. 9).
    """
    Xg = gridlib.cyclic_rows_device(X, p1 * p2, reverse=reverse)
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None else X.dtype
    Xg = Xg.astype(L_cyc.dtype)
    hp = gemm_precision(L_cyc, Xg)
    if L_cyc.ndim == 2:
        Y = jax.lax.dot(L_cyc, Xg, precision=hp,
                        preferred_element_type=acc)
    else:
        Y = jax.lax.dot_general(
            L_cyc, Xg, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            precision=hp, preferred_element_type=acc)
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
