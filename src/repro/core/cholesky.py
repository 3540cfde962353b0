"""Cholesky factorization built on the paper's primitives.

The paper's Sec. I motivation: "TRSM is used extensively ... to compute
factorizations with triangular matrices, such as Cholesky, LU, and QR."
This module closes that loop: a distributed Cholesky whose panel solve
is performed by *selective triangular inversion* (multiplication by an
inverted triangular factor) instead of substitution-based TRSM — i.e.
the paper's technique applied to its own motivating consumer.

  chol([[A11, .], [A21, A22]]):
      L11  = chol(A11)                        (recursive)
      L21  = A21 * L11^{-T}                   (invert + MM, Secs. V/III)
      A22' = A22 - L21 * L21^T                (MM, Sec. III)
      L22  = chol(A22')                       (recursive)

Also provides the local blocked factorization used by the KFAC-CA
optimizer (per-layer Kronecker factors), and the distributed transpose
for cyclic storage (1 permute + 1 all-to-all) used by the L11^{-T} and
L21^T steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat

from repro.core import blocked, comm
from repro.core import tri_inv as ti
from repro.core.grid import TrsmGrid
from repro.core.mm3d import mm3d_shard
from repro.core.precision import gemm_precision

MESH_AXES = ("x", "y", "z")


# ------------------------ local blocked Cholesky ------------------------

def chol_blocked_local(A: jnp.ndarray, bs: int) -> jnp.ndarray:
    """Left-looking blocked Cholesky; panel solve by multiplication with
    the inverted diagonal block (the paper's selective inversion)."""
    n = A.shape[-1]
    assert n % bs == 0, (n, bs)
    nb = n // bs
    L = jnp.zeros_like(A)
    hp = gemm_precision(A)
    for j in range(nb):
        s0, s1 = j * bs, (j + 1) * bs
        Ljl = L[s0:s1, :s0]
        Ajj = A[s0:s1, s0:s1] - jnp.matmul(Ljl, Ljl.T, precision=hp)
        Ljj = jnp.linalg.cholesky(Ajj)
        L = L.at[s0:s1, s0:s1].set(Ljj)
        if s1 < n:
            Pj = A[s1:, s0:s1] - jnp.matmul(L[s1:, :s0], Ljl.T,
                                            precision=hp)
            Ljj_inv = blocked.tri_inv_doubling(Ljj)
            L = L.at[s1:, s0:s1].set(
                jnp.matmul(Pj, Ljj_inv.T, precision=hp))
    return L


# -------------------- distributed cyclic-storage transpose --------------

def _swap_perm(p1: int):
    return [(x * p1 + y, y * p1 + x) for x in range(p1) for y in range(p1)]


def transpose_shard(Aloc, *, mr: int, nc: int, p1: int, p2: int):
    """Per-shard transpose: (mr x nc) cyclic piece -> (nc x mr) cyclic
    piece of A^T, same storage scheme.  1 ppermute + 1 all_to_all."""
    a, b = Aloc.shape                  # (mr/p1, nc/(p1 p2))
    assert a == mr // p1 and b == nc // (p1 * p2)
    Pc = comm.ppermute(Aloc, ("x", "y"), _swap_perm(p1)) if p1 > 1 else Aloc
    if p2 > 1:
        aq = a // p2
        Q = Pc.reshape(aq, p2, b).transpose(1, 0, 2)       # [z'', q, c']
        G = comm.all_to_all(Q, "z", split_axis=0, concat_axis=0,
                            tiled=True)                    # [z_src, q, c']
        G = G.reshape(p2, aq, b)
        T = G.transpose(2, 0, 1).reshape(b * p2, aq)       # [c'*p2+z, q]
    else:
        T = Pc.T
    return T


@functools.lru_cache(maxsize=64)
def transpose_fn(grid: TrsmGrid, mr: int, nc: int):
    body = functools.partial(transpose_shard, mr=mr, nc=nc,
                             p1=grid.p1, p2=grid.p2)
    spec = P("x", ("z", "y"))
    return jax.jit(compat.shard_map(body, mesh=grid.mesh, in_specs=(spec,),
                                 out_specs=spec))


# ---------------------- distributed recursive Cholesky ------------------

def _chol_base(Aloc, *, n, p1, p2):
    """Base case: allgather, factor locally, keep the cyclic piece."""
    xi = comm.axis_index("x")
    yi = comm.axis_index("y")
    zi = comm.axis_index("z")
    Ag = comm.all_gather(Aloc[None], MESH_AXES, axis=0, tiled=False)
    from repro.core.tri_inv import _assemble_blocks, _cyclic_piece
    Afull = _assemble_blocks(Ag, p1, p2)[0]            # (n, n)
    Lfull = jnp.linalg.cholesky(Afull)
    return _cyclic_piece(Lfull[None], xi, yi, zi, p1, p2)[0]


def _chol_rec(Aloc, *, n, n0, p1, p2):
    if n <= n0:
        return _chol_base(Aloc, n=n, p1=p1, p2=p2)
    h = n // 2
    hl, hc = h // p1, h // (p1 * p2)
    A11 = Aloc[:hl, :hc]
    A21 = Aloc[hl:, :hc]
    A22 = Aloc[hl:, hc:]
    L11 = _chol_rec(A11, n=h, n0=n0, p1=p1, p2=p2)
    # panel: L21 = A21 L11^{-T}  via selective inversion (no substitution)
    L11i = ti.tri_inv_shard(L11, n=h, p1=p1, p2=p2)
    L11iT = transpose_shard(L11i, mr=h, nc=h, p1=p1, p2=p2)
    L21 = mm3d_shard(A21, L11iT, m=h, n=h, k=h, p1=p1, p2=p2)
    # trailing update: A22 - L21 L21^T
    L21T = transpose_shard(L21, mr=h, nc=h, p1=p1, p2=p2)
    A22u = A22 - mm3d_shard(L21, L21T, m=h, n=h, k=h, p1=p1, p2=p2)
    L22 = _chol_rec(A22u, n=h, n0=n0, p1=p1, p2=p2)
    top = jnp.concatenate([L11, jnp.zeros((hl, hc), Aloc.dtype)], axis=1)
    bot = jnp.concatenate([L21, L22], axis=1)
    return jnp.concatenate([top, bot], axis=0)


@functools.lru_cache(maxsize=64)
def cholesky_fn(grid: TrsmGrid, n: int, n0: int | None = None):
    """Jitted distributed Cholesky for fixed shapes (cyclic storage).
    Memoized: repeated same-shape factorizations reuse the compiled
    program."""
    n0 = n0 or max(grid.p1 * grid.p1 * grid.p2, n // 8)
    while n % n0 != 0:
        n0 *= 2
    body = functools.partial(_chol_rec, n=n, n0=min(n0, n),
                             p1=grid.p1, p2=grid.p2)
    spec = P("x", ("z", "y"))
    return jax.jit(compat.shard_map(body, mesh=grid.mesh, in_specs=(spec,),
                                 out_specs=spec))


def cholesky_cyclic(A, grid: TrsmGrid, n0: int | None = None):
    """Factor A (natural layout, symmetric PD) and return L in CYCLIC
    storage — the factorization's own working layout, un-unpermuted.

    This is the factor-producer end of the paper's producer->consumer
    loop (Sec. I: "TRSM is used extensively ... Cholesky, LU, QR"): the
    result feeds ``repro.core.bank.FactorBank.admit_cyclic`` (or any
    cyclic-storage consumer) directly, with no unpermute -> re-permute
    round trip and no host traffic."""
    from repro.core.grid import cyclic_matrix_device
    n = A.shape[0]
    p1, p2 = grid.p1, grid.p2
    Ac = cyclic_matrix_device(jnp.asarray(A), p1, p1 * p2)
    return cholesky_fn(grid, n, n0)(Ac)


def cholesky(A, grid: TrsmGrid, n0: int | None = None):
    """Natural-layout convenience entry point (A symmetric PD).

    Device-resident: the cyclic permutations run as on-device gathers
    (repro.core.grid.cyclic_matrix_device) and the compiled program is
    memoized — no host round-trip, no per-call retrace.  For feeding a
    FactorBank keep the cyclic output instead: :func:`cholesky_cyclic`."""
    from repro.core.grid import cyclic_matrix_device
    p1, p2 = grid.p1, grid.p2
    Lc = cholesky_cyclic(A, grid, n0)
    return cyclic_matrix_device(Lc, p1, p1 * p2, inverse=True)
