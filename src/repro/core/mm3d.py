"""3D matrix multiplication from a 2D cyclic start (paper Sec. III).

Computes B = L @ X on the p1 x p1 x p2 mesh ("x", "y", "z") where all of
L, X and B live in the *same* cyclic storage scheme (see
``repro.core.grid``):

    rows cyclic over x  (global row r = l*p1 + x)
    cols cyclic over the pair t = z*p1 + y with stride p1*p2
        (global col c = c'*p1*p2 + z*p1 + y)

i.e. sharding spec ``P("x", ("z", "y"))`` for every operand.  Because
operand and result layouts coincide, MM calls compose (used heavily by
the distributed triangular inversion and the recursive TRSM).

Schedule (paper Alg. MM, adapted to the 3D mesh — see DESIGN.md):

    1. Lg = allgather(L, z)     -> L rows=x-residues, all cols = y-residues
       [cost  W = m*n/p1^2 * 1_{p2},  S = log p2]         (paper line 2)
    2. Xs = permute x<->y       -> X rows become y-residues
       [cost  W = n*k/p,  S = 1]                          (paper line 4)
    3. Xg = allgather(Xs, x)    -> X all cols of this z-slice, replicated x
       [cost  W = n*k/(p1*p2),  S = log p1]               (paper line 5)
    4. P  = Lg~ @ Xg            local GEMM
       [cost  F = m*n*k/p]                                (paper line 6)
    5. B  = reduce-scatter(P, y)  sum partials, keep col-chunk y
       [cost  W = F = m*k/(p1*p2),  S = log p1]           (paper line 7)

Our mesh-native layout removes the paper's lines 3 and 8 (the two
rectangular-grid transposes costing O(nk log(p)/p)): the reduce-scatter
lands directly on the input layout.  This is a (constant/log-factor)
improvement (DESIGN.md Sec. 8.2); the leading-order cost matches
the paper exactly:  W = mn/p1^2 * 1_{p2} + 2nk/(p1 p2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat

from repro.core import comm
from repro.core.grid import TrsmGrid, to_cyclic_matrix, from_cyclic_matrix
from repro.core.precision import gemm_precision


def _swap_perm(p1: int) -> list[tuple[int, int]]:
    """Permutation over the linearized ("x","y") pair sending (x,y)->(y,x)."""
    return [(x * p1 + y, y * p1 + x) for x in range(p1) for y in range(p1)]


def mm3d_shard(Lloc: jnp.ndarray, Xloc: jnp.ndarray, *,
               m: int, n: int, k: int, p1: int, p2: int,
               accum_dtype=None) -> jnp.ndarray:
    """Per-shard body (runs inside shard_map on the (x,y,z) mesh).

    Lloc: (m/p1, n/(p1*p2)) cyclic piece of the m x n left operand.
    Xloc: (n/p1, k/(p1*p2)) cyclic piece of the n x k right operand.
    Returns the (m/p1, k/(p1*p2)) cyclic piece of L @ X.
    ``accum_dtype``: GEMM/reduction precision (the local partial sums
    AND the cross-y reduce-scatter accumulate there); result is cast
    back to the operand dtype.
    """
    ml, ncl = Lloc.shape
    nl, kcl = Xloc.shape
    assert ml == m // p1 and ncl == n // (p1 * p2), (Lloc.shape, m, n, p1, p2)
    assert nl == n // p1 and kcl == k // (p1 * p2), (Xloc.shape, n, k, p1, p2)

    # 1. replicate L over z; realign gathered cols (z-major) to the
    #    X row order l = c'*p2 + z  (c'-major, z-minor).
    if p2 > 1:
        Lg = comm.all_gather(Lloc, "z", axis=1, tiled=True)  # (ml, p2*ncl)
        Lg = Lg.reshape(ml, p2, ncl).transpose(0, 2, 1).reshape(ml, ncl * p2)
    else:
        Lg = Lloc

    # 2-3. move X rows from x-residues to y-residues, then replicate the
    #      z-slice columns over x (cols end x'-major: col = x'*kcl + c').
    if p1 > 1:
        Xs = comm.ppermute(Xloc, ("x", "y"), _swap_perm(p1))
        Xg = comm.all_gather(Xs, "x", axis=1, tiled=True)    # (nl, p1*kcl)
    else:
        Xg = Xloc

    # 4. local GEMM: rows == x-residues, contraction over the y-residue
    #    class, cols = this z-slice.
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else Xloc.dtype
    Pp = jax.lax.dot(Lg, Xg, precision=gemm_precision(Lg, Xg),
                     preferred_element_type=acc)             # (ml, k/p2)

    # 5. complete the contraction over y; keep col-chunk x' == y, which
    #    is exactly the input cyclic layout.
    if p1 > 1:
        Bloc = comm.psum_scatter(Pp, "y", scatter_dimension=1, tiled=True)
    else:
        Bloc = Pp
    return Bloc.astype(Xloc.dtype)


def mm3d_shard_batched(Lloc, Xloc, *, m, n, k, p1, p2):
    """vmap of mm3d_shard over a leading batch axis (collectives batch)."""
    f = functools.partial(mm3d_shard, m=m, n=n, k=k, p1=p1, p2=p2)
    return jax.vmap(f)(Lloc, Xloc)


def mm3d_fn(grid: TrsmGrid, m: int, n: int, k: int):
    """Jitted distributed MM for fixed shapes, cyclic storage in/out."""
    body = functools.partial(mm3d_shard, m=m, n=n, k=k,
                             p1=grid.p1, p2=grid.p2)
    spec = P("x", ("z", "y"))
    fn = compat.shard_map(body, mesh=grid.mesh, in_specs=(spec, spec),
                       out_specs=spec)
    return jax.jit(fn)


def matmul(L, X, grid: TrsmGrid):
    """Convenience natural-layout entry point: returns L @ X.

    Applies the cyclic-storage permutation on the way in/out.  In real
    deployments operands are *kept* in cyclic storage across calls."""
    import numpy as np
    m, n = L.shape
    n2, k = X.shape
    assert n == n2
    p1, p2 = grid.p1, grid.p2
    Lc = to_cyclic_matrix(np.asarray(L), p1, p1 * p2)
    Xc = to_cyclic_matrix(np.asarray(X), p1, p1 * p2)
    Bc = mm3d_fn(grid, m, n, k)(Lc, Xc)
    return from_cyclic_matrix(np.asarray(Bc), p1, p1 * p2)
