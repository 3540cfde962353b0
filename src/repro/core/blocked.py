"""Local (single-device) blocked triangular primitives.

These are the numerical building blocks and oracles for the distributed
algorithms in this package:

* ``tri_inv_doubling`` — bottom-up ("recursive doubling") triangular
  inversion.  This is the SPMD-friendly re-derivation of the paper's
  RecTriInv (Sec. V): level ``l`` finalizes the off-diagonal block of every
  diagonal ``2^(l+1)``-block with two batched GEMMs
  (``inv([[A,0],[B,C]]) = [[A^-1,0],[-C^-1 B A^-1, C^-1]]``).
* ``block_diag_invert`` — invert only the ``n/n0`` diagonal blocks
  (the paper's Diagonal-Inverter output ``L~``).
* ``it_inv_trsm_local`` — the single-device schedule of It-Inv-TRSM
  (Sec. VI): multiply by pre-inverted diagonal blocks + trailing GEMM
  updates; no substitution in the sweep.
* ``rec_trsm_local`` — the recursive baseline (Sec. IV) with a
  substitution base case.
* reversal identities to reduce upper/transposed solves to the lower case.

Everything is pure jnp and jit-friendly (static shapes, lax control flow).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.precision import gemm_precision


def next_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def diag_blocks(A: jnp.ndarray, a: int, b: int) -> jnp.ndarray:
    """The m = rows/a diagonal windows ``A[i*a:(i+1)*a, i*b:(i+1)*b]``
    of a 2-D array, as ONE windowed gather -> (m, a, b).  (The
    advanced-index spelling ``A.reshape(m, a, m, b)[i, :, i, :]``
    lowers through a transposed copy of the whole matrix on the TPU:
    twice its bytes of scratch, past HBM for a chip-sized factor.)"""
    m = A.shape[0] // a
    return jax.vmap(lambda i: jax.lax.dynamic_slice(
        A, (i * a, i * b), (a, b)))(jnp.arange(m))


def set_diag_blocks(A: jnp.ndarray, blocks: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`diag_blocks`: write the (m, a, b) windows back
    along the diagonal with one windowed scatter."""
    m, a, b = blocks.shape
    i = jnp.arange(m)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0, 1))
    return jax.lax.scatter(A, jnp.stack([i * a, i * b], axis=1), blocks,
                           dnums, indices_are_sorted=True,
                           unique_indices=True)


# Diagonal tiles up to this order are inverted by masked full-tile
# GEMMs (tri_inv_tile); the doubling levels above it gather diagonal
# blocks whose minor dimension is then >= 2 * _TILE.  Gathering the
# small levels instead would lay out (.., 2s, .., 2s) views with minor
# dimension 2s padded to the TPU's 128-lane tile: 64x the bytes at
# s = 1, past the chip's HBM at n0 in the thousands.
_TILE = 128


def tri_inv_tile(L: jnp.ndarray, accum_dtype=None) -> jnp.ndarray:
    """Invert lower-triangular (b, b) tiles (any leading batch axes; b
    a power of two) by bottom-up doubling on WHOLE tiles: at level s,
    with ``Dm`` the block diagonal of the already-inverted s-blocks and
    ``Lm`` the original (2,1) sub-blocks of every diagonal 2s-block
    (both iota masks, no gathers or unaligned slices),
    ``Dm @ (Lm @ Dm)`` is supported exactly on those (2,1) positions
    and equals ``C^-1 B A^-1`` there.  Two GEMMs per level, at
    ``accum_dtype`` (default: the operand dtype).  The strictly upper
    triangle is read as zero.  This is also the body of the Pallas
    inverter (repro.kernels.tri_inv_block)."""
    b = L.shape[-1]
    dt = L.dtype
    acc = dt if accum_dtype is None else accum_dtype
    hp = gemm_precision(dt)
    row = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    zero = jnp.zeros_like(L)
    A = jnp.where(row > col, L, zero)
    A = jnp.where(row == col, 1.0 / L, A)          # level 0: 1x1 blocks
    shift = 0
    while (1 << shift) < b:
        rb = row >> shift                  # s-block indices, s = 2**shift
        cb = col >> shift
        Dm = jnp.where(rb == cb, A, zero)
        in21 = (rb == cb + 1) & ((rb & 1) == 1)
        Lm = jnp.where(in21, A, zero)
        t = jnp.matmul(Lm, Dm, precision=hp,
                       preferred_element_type=acc).astype(dt)
        n21 = jnp.matmul(Dm, t, precision=hp,
                         preferred_element_type=acc).astype(dt)
        A = jnp.where(in21, -n21, A)
        shift += 1
    return A


def tri_inv_doubling(L: jnp.ndarray) -> jnp.ndarray:
    """Invert a lower-triangular matrix by bottom-up block doubling.

    Cost-identical to the paper's RecTriInv but single-program: log2(n)
    levels, each two batched GEMMs over all off-diagonal blocks at that
    level — the levels inside the diagonal ``_TILE``-blocks on whole
    tiles (:func:`tri_inv_tile`).  Pads to the next power of two with
    an identity block (``inv([[L,0],[0,I]]) = [[L^-1,0],[0,I]]``).
    The strictly upper triangle is read as zero.
    """
    n = L.shape[-1]
    N = next_pow2(n)
    L = jnp.tril(L)
    if N != n:
        Lp = jnp.eye(N, dtype=L.dtype)
        L = Lp.at[:n, :n].set(L)
    t = min(N, _TILE)
    A = set_diag_blocks(L, tri_inv_tile(diag_blocks(L, t, t)))
    s = t
    hp = gemm_precision(A)
    while s < N:
        blk = diag_blocks(A, 2 * s, 2 * s)   # (nb, 2s, 2s)
        a11i = blk[:, :s, :s]                  # already inverted
        a22i = blk[:, s:, s:]                  # already inverted
        l21 = blk[:, s:, :s]                   # still original L entries
        new21 = -jnp.einsum("bij,bjk,bkl->bil", a22i, l21, a11i,
                            precision=hp)
        blk = blk.at[:, s:, :s].set(new21)
        A = set_diag_blocks(A, blk)
        s *= 2
    return A[:n, :n] if N != n else A


def tri_inv_batched(Ls: jnp.ndarray) -> jnp.ndarray:
    """vmap of tri_inv_doubling over a stack (m, n0, n0)."""
    return jax.vmap(tri_inv_doubling)(Ls)


def block_diag_invert(L: jnp.ndarray, n0: int) -> jnp.ndarray:
    """Return L~: L with every (n0 x n0) diagonal block inverted in place.

    This is the output contract of the paper's Diagonal-Inverter: the
    off-diagonal panels are untouched; only diagonal blocks are inverted.
    """
    n = L.shape[-1]
    assert n % n0 == 0, (n, n0)
    blocks = diag_blocks(L, n0, n0)
    inv = tri_inv_batched(blocks)
    return set_diag_blocks(L, inv)


def it_inv_trsm_local(L: jnp.ndarray, B: jnp.ndarray, n0: int,
                      block_inv=None) -> jnp.ndarray:
    """It-Inv-TRSM (paper Sec. VI) on one device: solve L X = B.

    1. Invert diagonal n0-blocks ("inversion" phase).
    2. Sweep i = 0..n/n0-1:  X_i = L~_ii @ B_i   (GEMM, not substitution)
       then the trailing update B_{>i} -= L[:, S_i] @ X_i  (GEMM),
       masked to rows > (i+1) n0 (the paper's T_{i+1} update range,
       expressed with static shapes for SPMD/jit friendliness).

    ``block_inv``: optional override for the batched diagonal-block
    inverter (e.g. the Pallas kernel); defaults to tri_inv_batched.
    """
    n = L.shape[-1]
    k = B.shape[-1]
    assert n % n0 == 0
    m = n // n0
    inv_fn = block_inv if block_inv is not None else tri_inv_batched
    dblocks = inv_fn(diag_blocks(L, n0, n0))   # (m, n0, n0) inverted

    row_ids = jnp.arange(n)

    def body(i, carry):
        B_cur, X = carry
        Bi = jax.lax.dynamic_slice(B_cur, (i * n0, 0), (n0, k))
        Xi = jnp.matmul(dblocks[i], Bi,
                        precision=gemm_precision(dblocks, Bi))  # solve: GEMM
        X = jax.lax.dynamic_update_slice(X, Xi, (i * n0, 0))
        panel = jax.lax.dynamic_slice(L, (0, i * n0), (n, n0))  # L[:, S_i]
        mask = (row_ids >= (i + 1) * n0).astype(L.dtype)[:, None]
        B_cur = B_cur - mask * jnp.matmul(
            panel, Xi, precision=gemm_precision(panel, Xi))
        return B_cur, X

    _, X = jax.lax.fori_loop(0, m, body, (B, jnp.zeros_like(B)))
    return X


def rec_trsm_local(L: jnp.ndarray, B: jnp.ndarray, n0: int) -> jnp.ndarray:
    """Recursive TRSM baseline (paper Sec. IV) on one device.

    Splits L into quadrants until n <= n0, base case = forward
    substitution (jax.scipy solve_triangular).  Python recursion over
    static shapes — unrolled at trace time, as in the paper's recursion.
    """
    n = L.shape[-1]
    if n <= n0:
        return jax.scipy.linalg.solve_triangular(L, B, lower=True)
    h = n // 2
    L11, L21, L22 = L[:h, :h], L[h:, :h], L[h:, h:]
    X1 = rec_trsm_local(L11, B[:h], n0)
    B2 = B[h:] - jnp.matmul(L21, X1, precision=gemm_precision(L21, X1))
    X2 = rec_trsm_local(L22, B2, n0)
    return jnp.concatenate([X1, X2], axis=0)


def forward_substitution(L: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """Row-by-row forward substitution (the latency/VPU-bound baseline
    that the paper's inversion approach replaces).  Reference only."""
    n = L.shape[-1]

    def body(i, X):
        xi = (B[i] - jnp.matmul(L[i], X, precision=gemm_precision(L, X))
              ) / L[i, i]
        return X.at[i].set(xi)

    return jax.lax.fori_loop(0, n, body, jnp.zeros_like(B))


# ----- reductions of the other triangular cases to the lower-left one -----

def solve_lower(L, B, solver, **kw):
    return solver(L, B, **kw)


def solve_upper(U, B, solver, **kw):
    """U X = B via the reversal identity: J U J is lower-triangular."""
    Lr = U[::-1, ::-1]
    return solver(Lr, B[::-1], **kw)[::-1]


def solve_lower_t(L, B, solver, **kw):
    """L^T X = B (upper solve with the lower factor) via reversal."""
    return solve_upper(L.T, B, solver, **kw)


def spd_solve(L_chol, B, solver, **kw):
    """A^-1 B given A = L L^T: two triangular solves (the K-FAC use)."""
    Y = solve_lower(L_chol, B, solver, **kw)
    return solve_lower_t(L_chol, Y, solver, **kw)
