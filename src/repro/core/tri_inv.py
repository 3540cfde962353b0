"""Distributed triangular inversion (paper Sec. V), SPMD bottom-up.

The paper's RecTriInv recursively splits L into quadrants, inverts the
two diagonal quadrants on *disjoint* processor subgrids, and completes
the off-diagonal block with two matrix multiplications:

    inv([[A, 0], [B, C]]) = [[inv(A), 0], [-inv(C) B inv(A), inv(C)]]

Divergent per-subgrid control flow does not fit SPMD, so we re-derive
the algorithm *bottom-up* ("recursive doubling"), which is the exact
mirror of the recursion tree executed level by level from the leaves:

  Phase A  invert all n/s0 diagonal s0-blocks in parallel (route whole
           blocks to devices with one all-to-all when n/s0 >= p — the
           TPU-native replacement for the paper's per-subgrid
           recursion; allgather fallback otherwise).
  Phase B  for s = s0, 2*s0, ..., n/2: finalize the off-diagonal block
           of every diagonal 2s-block with two *batched* distributed
           MMs (Sec. III algorithm, vmapped over the n/2s independent
           blocks; the batch plays the role of the paper's disjoint
           subgrids — all p processors cooperate on all blocks, which
           achieves a slightly *lower* bandwidth constant than the
           paper's shrinking-subgrid scheme; see DESIGN.md Sec. 8.3).

Latency is O(log(n/s0)) levels x O(log p) per level = O(log^2 p) — the
paper's headline polylog synchronization — and the flop/bandwidth costs
match Sec. V-B leading order.

Storage: cyclic, ``P("x", ("z", "y"))`` (see repro.core.grid).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat

from repro.core import blocked, comm
from repro.core import grid as gridlib
from repro.core.grid import TrsmGrid
from repro.core.mm3d import mm3d_shard_batched

MESH_AXES = ("x", "y", "z")


# ------------------------ local-piece helpers ------------------------

def _diag_pieces(Lloc, m: int):
    """(nl, ncl) local cyclic piece -> (m, nl/m, ncl/m) local pieces of
    the m diagonal blocks."""
    nl, ncl = Lloc.shape
    return blocked.diag_blocks(Lloc, nl // m, ncl // m)


def _assemble_blocks(Dg, p1: int, p2: int):
    """(p, m, a, b) gathered pieces (x-major flattened device axis) ->
    (m, a*p1, b*p1*p2) full blocks in natural element order."""
    return gridlib.assemble_blocks(Dg, p1, p2)


def _cyclic_piece(blocks, x, y, z, p1: int, p2: int):
    """(m, s, s) full blocks -> this device's cyclic piece
    (rows r = l*p1 + x, cols c = c'*p1*p2 + z*p1 + y): (m, s/p1, s/(p1p2)).
    x, y, z may be traced scalars."""
    return gridlib.block_piece(blocks, x, z * p1 + y, p1, p1 * p2)


def _pieces_for_all(blocks, p1: int, p2: int):
    """(m, s, s) full blocks -> (p, m, s/p1, s/(p1p2)) cyclic pieces for
    every destination device, x-major device order."""
    m, s, _ = blocks.shape
    R = gridlib.block_pieces(blocks, p1, p1 * p2)  # [x, (z, y), i, l, c']
    R = R.reshape(p1, p2, p1, m, s // p1, s // (p1 * p2))
    R = jnp.transpose(R, (0, 2, 1, 3, 4, 5))       # [x, y, z, i, l, c']
    return R.reshape(p1 * p1 * p2, m, s // p1, s // (p1 * p2))


# --------------------------- phase A ---------------------------

def _invert_diag_blocks_inplace(Lloc, *, n, s0, p1, p2, block_inv, mode):
    """Invert the n/s0 diagonal s0-blocks of L; return updated Lloc with
    the inverted blocks written back into cyclic storage."""
    m0 = n // s0
    p = p1 * p1 * p2
    D = _diag_pieces(Lloc, m0)                     # (m0, a, b)

    if mode == "alltoall":
        assert m0 % p == 0, (m0, p)
        mb = m0 // p
        Dr = comm.all_to_all(D, MESH_AXES, split_axis=0, concat_axis=0,
                             tiled=True)           # (m0, a, b) regrouped
        Dr = Dr.reshape(p, mb, *Dr.shape[1:])
        blocks = _assemble_blocks(Dr, p1, p2)      # (mb, s0, s0)
        binv = block_inv(blocks)
        S = _pieces_for_all(binv, p1, p2)          # (p, mb, a, b)
        Dt = comm.all_to_all(S.reshape(m0, *S.shape[2:]), MESH_AXES,
                             split_axis=0, concat_axis=0, tiled=True)
        return blocked.set_diag_blocks(Lloc, Dt)
    elif mode == "allgather":
        xi = comm.axis_index("x")
        yi = comm.axis_index("y")
        zi = comm.axis_index("z")
        Dg = comm.all_gather(D, MESH_AXES, axis=0, tiled=False)
        blocks = _assemble_blocks(Dg, p1, p2)      # (m0, s0, s0)
        binv = block_inv(blocks)
        piece = _cyclic_piece(binv, xi, yi, zi, p1, p2)
        return blocked.set_diag_blocks(Lloc, piece)
    raise ValueError(mode)


# --------------------------- phase B ---------------------------

def _doubling_levels(Lloc, *, n, s0, s_hi, p1, p2):
    """Run doubling levels s = s0 .. s_hi/2, finalizing off-diagonal
    blocks of every diagonal 2s-block up to block size s_hi."""
    s = s0
    while s < s_hi:
        nb = n // (2 * s)
        al, bl = 2 * s // p1, 2 * s // (p1 * p2)   # local piece dims
        blk = _diag_pieces(Lloc, nb)               # (nb, al, bl)
        a11 = blk[:, : al // 2, : bl // 2]         # inverted already
        a22 = blk[:, al // 2:, bl // 2:]           # inverted already
        l21 = blk[:, al // 2:, : bl // 2]          # original entries
        T = mm3d_shard_batched(l21, a11, m=s, n=s, k=s, p1=p1, p2=p2)
        new21 = -mm3d_shard_batched(a22, T, m=s, n=s, k=s, p1=p1, p2=p2)
        blk = blk.at[:, al // 2:, : bl // 2].set(new21)
        Lloc = blocked.set_diag_blocks(Lloc, blk)
        s *= 2
    return Lloc


# --------------------------- entry points ---------------------------

def pick_s0(n: int, p1: int, p2: int) -> int:
    """Base block size: prefer m0 = n/s0 == p (one block per device,
    all-to-all routing); fall back to the smallest feasible block."""
    p = p1 * p1 * p2
    gran = p1 * p2
    if n % p == 0:
        s0 = n // p
        if s0 % gran == 0 and s0 >= gran:
            return s0
    s0 = gran
    while n % s0 != 0 and s0 < n:
        s0 *= 2
    return min(s0, n)


def phase_a_mode(n: int, s0: int, p: int) -> str:
    m0 = n // s0
    return "alltoall" if m0 % p == 0 else "allgather"


def tri_inv_shard(Lloc, *, n, p1, p2, s0=None, block_inv=None,
                  mode=None):
    """Per-shard body: full triangular inversion in cyclic storage."""
    s0 = s0 or pick_s0(n, p1, p2)
    mode = mode or phase_a_mode(n, s0, p1 * p1 * p2)
    binv = block_inv if block_inv is not None else blocked.tri_inv_batched
    Lloc = _invert_diag_blocks_inplace(Lloc, n=n, s0=s0, p1=p1, p2=p2,
                                       block_inv=binv, mode=mode)
    return _doubling_levels(Lloc, n=n, s0=s0, s_hi=n, p1=p1, p2=p2)


def block_diag_inv_shard(Lloc, *, n, n0, p1, p2, s0=None, block_inv=None,
                         mode=None):
    """Per-shard body: invert only the n/n0 diagonal n0-blocks (the
    paper's Diagonal-Inverter) using the same two-phase scheme, with
    doubling stopped at block size n0.  Off-diagonal panels between
    n0-blocks are untouched."""
    s0 = s0 or pick_s0(n, p1, p2)
    s0 = min(s0, n0)
    mode = mode or phase_a_mode(n, s0, p1 * p1 * p2)
    binv = block_inv if block_inv is not None else blocked.tri_inv_batched
    Lloc = _invert_diag_blocks_inplace(Lloc, n=n, s0=s0, p1=p1, p2=p2,
                                       block_inv=binv, mode=mode)
    if s0 < n0:
        nb = n // n0
        al, bl = n0 // p1, n0 // (p1 * p2)
        blk = _diag_pieces(Lloc, nb)
        # run the doubling levels on each n0-block independently by
        # flattening (n0-block, inner 2s-group) into one batch axis:
        s = s0
        while s < n0:
            inner = n0 // (2 * s)
            a2, b2 = 2 * s // p1, 2 * s // (p1 * p2)
            sub = blk.reshape(nb, inner, a2, inner, b2)
            idx = jnp.arange(inner)
            d = sub[:, idx, :, idx, :]             # (inner, nb, a2, b2)
            d = jnp.moveaxis(d, 0, 1).reshape(nb * inner, a2, b2)
            a11 = d[:, : a2 // 2, : b2 // 2]
            a22 = d[:, a2 // 2:, b2 // 2:]
            l21 = d[:, a2 // 2:, : b2 // 2]
            T = mm3d_shard_batched(l21, a11, m=s, n=s, k=s, p1=p1, p2=p2)
            new21 = -mm3d_shard_batched(a22, T, m=s, n=s, k=s,
                                        p1=p1, p2=p2)
            d = d.at[:, a2 // 2:, : b2 // 2].set(new21)
            d = jnp.moveaxis(d.reshape(nb, inner, a2, b2), 1, 0)
            sub = sub.at[:, idx, :, idx, :].set(d)
            blk = sub.reshape(nb, al, bl)
            s *= 2
        Lloc = blocked.set_diag_blocks(Lloc, blk)
    return Lloc


def tri_inv_fn(grid: TrsmGrid, n: int, s0: int | None = None,
               block_inv=None, mode: str | None = None):
    """Jitted distributed inversion for fixed shapes (cyclic storage)."""
    body = functools.partial(tri_inv_shard, n=n, p1=grid.p1, p2=grid.p2,
                             s0=s0, block_inv=block_inv, mode=mode)
    spec = P("x", ("z", "y"))
    # off only for a Pallas hook: see inv_trsm.it_inv_trsm_sharded
    fn = compat.shard_map(body, mesh=grid.mesh, in_specs=(spec,),
                          out_specs=spec, check_vma=block_inv is None)
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _invert_fn(grid: TrsmGrid, n: int, s0, mode):
    return tri_inv_fn(grid, n, s0=s0, mode=mode)


def invert(L, grid: TrsmGrid, s0: int | None = None, mode=None):
    """Natural-layout convenience entry point (device-resident: on-device
    cyclic permutations, memoized compiled program)."""
    from repro.core.grid import cyclic_matrix_device
    n = L.shape[0]
    p1, p2 = grid.p1, grid.p2
    Lc = cyclic_matrix_device(jnp.asarray(L), p1, p1 * p2)
    out = _invert_fn(grid, n, s0, mode)(Lc)
    return cyclic_matrix_device(out, p1, p1 * p2, inverse=True)
