"""The plain reference: float64 normwise backward error on the host,
and the plain blocked solve that stands in for the program in a control
run (every product of it at the precision the control names, the
diagonal blocks too).  Imports nothing of the program.

The backward error of an answer X to L X = B is

    ||L X - B||_inf / (||L||_inf ||X||_inf + ||B||_inf),

computed in float64 from the float32 factor (exact in float64), reading
L in row chunks so no n x n float64 array ever exists.  A sampled
answer (some columns of a block) is judged as the sub-problem
L X_S = B_S of its columns, which has the same bound.
"""

from __future__ import annotations

import collections
import concurrent.futures

import jax
import jax.numpy as jnp
import numpy as np


def backward_errors(row_chunks, X, B, edges, workers: int = 8) -> list:
    """Backward error of each answer.

    ``row_chunks`` yields ``(r0, L[r0:r1])`` covering every row of L
    once (any float dtype); chunks are reduced on ``workers`` threads,
    a bounded number at a time.  ``X`` and ``B`` hold the answers'
    columns side by side, answer j in columns ``edges[j]:edges[j+1]``.
    An answer that is not finite reads inf."""
    X = np.asarray(X, np.float64)
    B = np.asarray(B, np.float64)
    starts = np.asarray(edges[:-1])
    finite = np.isfinite(X).all(axis=0)
    Xf = np.where(np.isfinite(X), X, 0.0)

    def part(item):
        r0, Lc = item
        Lc = np.asarray(Lc, np.float64)
        R = np.abs(Lc @ Xf - B[r0:r0 + Lc.shape[0]])
        return (np.abs(Lc).sum(1).max(),
                np.add.reduceat(R, starts, axis=1).max(axis=0))

    lnorm, res = 0.0, np.zeros(len(starts))
    with concurrent.futures.ThreadPoolExecutor(workers) as ex:
        pending = collections.deque()
        for item in row_chunks:
            pending.append(ex.submit(part, item))
            while len(pending) > 2 * workers \
                    or (pending and pending[0].done()):
                ln, r = pending.popleft().result()
                lnorm, res = max(lnorm, ln), np.maximum(res, r)
        for f in pending:
            ln, r = f.result()
            lnorm, res = max(lnorm, ln), np.maximum(res, r)
    out = []
    for j, (e0, e1) in enumerate(zip(edges[:-1], edges[1:])):
        if not finite[e0:e1].all():
            out.append(float("inf"))
            continue
        xn = np.abs(X[:, e0:e1]).sum(1).max()
        bn = np.abs(B[:, e0:e1]).sum(1).max()
        out.append(float(res[j] / (lnorm * xn + bn)))
    return out


def dot_high(a, b):
    """A float32 product as ``Precision.HIGH`` computes it on the MXU:
    three bf16 passes (hi*hi + hi*lo + lo*hi) accumulated in float32.
    Written out, so it means the same on every platform."""
    def split(x):
        # reduce_precision, not a round trip through bfloat16: XLA may
        # drop a convert pair that loses precision, which leaves lo = 0
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)
    (ah, al), (bh, bl) = split(a), split(b)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def _substitute(Lb, R, dot):
    """Row-by-row substitution in one diagonal block, every row's dot
    product by ``dot``."""
    b = Lb.shape[-1]

    def row(r, X):
        lr = jax.lax.dynamic_slice_in_dim(Lb, r, 1, axis=-2)
        x = (jax.lax.dynamic_slice_in_dim(R, r, 1, axis=-2) - dot(lr, X)) \
            / jax.lax.dynamic_slice_in_dim(lr, r, 1, axis=-1)
        return jax.lax.dynamic_update_slice_in_dim(X, x, r, axis=-2)
    # unsolved rows of X are zero, so the whole row of Lb can enter
    return jax.lax.fori_loop(0, b, row, jnp.zeros_like(R))


def plain_solve(L, B, *, block: int, dot):
    """Blocked forward substitution L X = B for a lower (..., n, n)
    factor, every product by ``dot``: each block row subtracts the
    solved part, then substitutes row by row in its diagonal block."""
    n = L.shape[-1]
    xs = []
    for r0 in range(0, n, block):
        r1 = r0 + block
        R = B[..., r0:r1, :]
        if xs:
            R = R - dot(L[..., r0:r1, :r0], jnp.concatenate(xs, axis=-2))
        xs.append(_substitute(L[..., r0:r1, r0:r1], R, dot))
    return jnp.concatenate(xs, axis=-2)


def plain_solve_transposed(L, B, *, block: int, dot):
    """L^T X = B for a lower (..., n, n) factor: the forward
    substitution of the reversed system, (J L^T J)(J X) = J B."""
    U = jnp.flip(jnp.swapaxes(L, -1, -2), (-2, -1))
    return jnp.flip(plain_solve(U, jnp.flip(B, -2), block=block, dot=dot),
                    -2)
