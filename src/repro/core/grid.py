"""Processor grids and cyclic layouts for the TRSM algorithms.

The paper runs on a p1 x p1 x p2 grid with *cyclic* data layouts (the
triangular structure makes blocked layouts load-imbalanced and, more
importantly, the iterative sweep requires every rank to own a piece of
every diagonal block).  XLA shards arrays in contiguous blocks, so the
cyclic layout is realized as *permuted storage* (exactly ScaLAPACK-style
block-cyclic storage): the global array is stored row/column-permuted so
that a contiguous block shard corresponds to a stride-p cyclic index set.

Conventions used by all distributed algorithms in repro.core:

* mesh axes ("x", "y", "z") with sizes (p1, p1, p2)
* L: rows cyclic over x (global row g = l*p1 + x), columns cyclic over
  the pair rank t = z*p1 + y with stride p1*p2 (global col c_g =
  c*p1*p2 + z*p1 + y)  ->  storage sharded P("x", ("z", "y"))
* B: rows cyclic over x, columns blocked over z -> P("x", "z"), and
  replicated over y
* X (output): rows cyclic over *y* (a property of the paper's solve
  step: the allreduce over x leaves X on the transposed face),
  columns blocked over z -> P("y", "z")
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TrsmGrid:
    mesh: Mesh
    p1: int
    p2: int

    @property
    def p(self) -> int:
        return self.p1 * self.p1 * self.p2

    def spec_L(self):
        return P("x", ("z", "y"))

    def spec_B(self):
        return P("x", "z")

    def spec_X(self):
        return P("y", "z")


def make_trsm_mesh(p1: int, p2: int, devices=None) -> TrsmGrid:
    devices = np.asarray(devices if devices is not None else jax.devices())
    p = p1 * p1 * p2
    assert devices.size >= p, (devices.size, p)
    mesh = Mesh(devices.reshape(-1)[:p].reshape(p1, p1, p2),
                axis_names=("x", "y", "z"))
    return TrsmGrid(mesh, p1, p2)


# ------------------------- cyclic storage helpers -------------------------

def cyclic_perm(n: int, p: int) -> np.ndarray:
    """Permutation mapping storage order -> global index for a stride-p
    cyclic layout: storage position (chunk r, slot l) holds global r + l*p."""
    return np.concatenate([np.arange(r, n, p) for r in range(p)])


def inv_perm(perm: np.ndarray) -> np.ndarray:
    out = np.empty_like(perm)
    out[perm] = np.arange(perm.size)
    return out


def to_cyclic_rows(a, p: int):
    """Natural -> cyclic storage along axis 0."""
    return a[cyclic_perm(a.shape[0], p)]


def from_cyclic_rows(a, p: int):
    return a[inv_perm(cyclic_perm(a.shape[0], p))]


def to_cyclic_matrix(L, p_row: int, p_col: int):
    """Natural -> cyclic storage for a matrix (rows stride p_row, cols
    stride p_col).  NOTE: this changes storage, not the operator: the
    algorithms index shards with the cyclic map, so correctness is
    preserved without the matrix being triangular in storage."""
    pr = cyclic_perm(L.shape[0], p_row)
    pc = cyclic_perm(L.shape[1], p_col)
    return L[pr][:, pc]


def from_cyclic_matrix(L, p_row: int, p_col: int):
    pr = inv_perm(cyclic_perm(L.shape[0], p_row))
    pc = inv_perm(cyclic_perm(L.shape[1], p_col))
    return L[pr][:, pc]


def cyclic_row_index(n: int, p: int, *, inverse: bool = False,
                     reverse: bool = False) -> np.ndarray:
    """Gather index realizing the cyclic-storage permutation along one
    axis, optionally composed with the reversal identity (the upper /
    transposed-solve reduction, DESIGN.md Sec. 3) into a SINGLE gather.

    forward (natural -> cyclic):  out[i] = a[idx[i]], idx = perm or
        (n-1-perm) when ``reverse`` (cyclic storage of the reversed
        array a[::-1]).
    inverse (cyclic -> natural):  idx = perm^-1, or perm^-1 reversed
        when ``reverse`` (natural layout of the reversed solution).
    The two compose to the identity for matching flags."""
    perm = cyclic_perm(n, p)
    if inverse:
        idx = inv_perm(perm)
        return np.ascontiguousarray(idx[::-1]) if reverse else idx
    return (n - 1 - perm) if reverse else perm


@functools.partial(jax.jit, static_argnames=("p", "inverse", "reverse"))
def cyclic_rows_device(a, p: int, *, inverse: bool = False,
                       reverse: bool = False):
    """On-device natural <-> cyclic storage permutation along the row
    axis (axis 0 for an (n, k) operand; axis -2 for a stacked
    (..., n, k) operand, so one gather permutes a whole factor bank's
    worth of right-hand sides).

    The jitted equivalent of :func:`to_cyclic_rows` /
    :func:`from_cyclic_rows`: one gather, computed where the operand
    lives (XLA turns the static index array into a data-movement-only
    program; under GSPMD the gather is partitioned over the mesh), so
    the solve pipeline never bounces rows through host NumPy."""
    if p == 1 and not reverse:
        return a                       # identity permutation: no gather
    axis = max(a.ndim - 2, 0)
    idx = cyclic_row_index(a.shape[axis], p, inverse=inverse,
                           reverse=reverse)
    return jnp.take(a, jnp.asarray(idx), axis=axis)


@functools.partial(jax.jit, static_argnames=(
    "p_row", "p_col", "inverse", "reverse_rows", "reverse_cols",
    "transpose"))
def cyclic_matrix_device(A, p_row: int, p_col: int, *,
                         inverse: bool = False, reverse_rows: bool = False,
                         reverse_cols: bool = False, transpose: bool = False):
    """On-device natural <-> cyclic storage permutation for a matrix,
    or for a STACK of matrices (leading batch axes: the permutations
    apply to the trailing two axes, so a factor bank's (M, n, n) stack
    is distributed by the same single fused gather program).

    Composes (optional) transposition and (optional) per-axis reversal
    with the two cyclic gathers, so an upper/transposed factor is
    distributed with the same single fused program as a lower one.
    ``transpose`` is applied before the row/col permutations (forward)
    — it is only meaningful for the forward direction, where the
    operator reductions L^T / JUJ are folded into distribution."""
    if transpose:
        A = jnp.swapaxes(A, -2, -1)
    if p_row > 1 or reverse_rows:      # p == 1 without reversal is the
        ri = cyclic_row_index(A.shape[-2], p_row, inverse=inverse,
                              reverse=reverse_rows)
        A = jnp.take(A, jnp.asarray(ri), axis=-2)
    if p_col > 1 or reverse_cols:      # identity: skip the gather
        ci = cyclic_row_index(A.shape[-1], p_col, inverse=inverse,
                              reverse=reverse_cols)
        A = jnp.take(A, jnp.asarray(ci), axis=-1)
    return A


# ------------------ diagonal blocks from cyclic pieces ------------------
#
# Phase 1 gathers the cyclic pieces of whole diagonal blocks, inverts
# the blocks in natural order, and sends pieces back.  Spelled as a
# reshape and transpose, the interleave g = l*p + r puts the size-p
# axis minor to l, and the TPU's (8, 128) tiling pads that axis to 8
# sublanes or 128 lanes: up to 64x the block's bytes.  These helpers
# instead lay whole pieces side by side in cyclic storage order and
# undo the storage order with the static gathers of
# cyclic_matrix_device, so every intermediate keeps the pieces' own
# (large) axes minor.

def assemble_blocks(pieces, p1: int, p2: int):
    """(p, m, a, b) cyclic pieces, one per device of the (x, y, z) mesh
    in x-major order -> (m, a*p1, b*p1*p2) whole blocks in natural
    order: rows l*p1 + x, columns c*p1*p2 + z*p1 + y."""
    p, m, a, b = pieces.shape
    if p == 1:
        return pieces.reshape(m, a, b)
    R = pieces.reshape(p1, p1, p2, m, a, b)        # [x, y, z, i, l, c]
    S = jnp.concatenate([                          # rows x-major, columns
        jnp.concatenate([R[x, y, z] for z in range(p2)   # (z, y)-major
                         for y in range(p1)], axis=-1)
        for x in range(p1)], axis=-2)
    return cyclic_matrix_device(S, p1, p1 * p2, inverse=True)


def block_pieces(blocks, p_row: int, p_col: int):
    """(m, s, t) natural-order blocks -> (p_row, p_col, m, s/p_row,
    t/p_col): piece [r, c] holds the rows = r (mod p_row) and the
    columns = c (mod p_col) of every block."""
    m, s, t = blocks.shape
    T = cyclic_matrix_device(blocks, p_row, p_col)
    T = T.reshape(m, p_row, s // p_row, p_col, t // p_col)
    return jnp.transpose(T, (1, 3, 0, 2, 4))


def block_piece(blocks, row_off, col_off, p_row: int, p_col: int):
    """One piece of :func:`block_pieces`, at offsets that may be
    traced: (m, s/p_row, t/p_col)."""
    _, s, t = blocks.shape
    a, b = s // p_row, t // p_col
    T = cyclic_matrix_device(blocks, p_row, p_col)
    T = jax.lax.dynamic_slice_in_dim(T, row_off * a, a, axis=1)
    return jax.lax.dynamic_slice_in_dim(T, col_off * b, b, axis=2)


def shard(grid: TrsmGrid, arr, spec):
    return jax.device_put(arr, NamedSharding(grid.mesh, spec))


def check_divisibility(n: int, k: int, n0: int, grid: TrsmGrid) -> None:
    p1, p2 = grid.p1, grid.p2
    assert n % n0 == 0, (n, n0)
    assert n0 % (p1 * p2) == 0, ("need p1*p2 | n0 for contiguous local "
                                 "diagonal blocks", n0, p1, p2)
    assert k % p2 == 0, (k, p2)
    # any block count m = n/n0 is supported: phase 1 picks alltoall
    # (p | m), cooperative doubling (m < p), or the allgather fallback.
