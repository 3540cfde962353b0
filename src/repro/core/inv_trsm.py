"""Distributed It-Inv-TRSM (the paper's main contribution, Secs. VI-VII).

shard_map implementation on a p1 x p1 x p2 mesh ("x", "y", "z"), cyclic
storage per repro.core.grid.  Two phases:

1. *Diagonal-Inverter*: the n/n0 diagonal blocks of L are inverted in
   parallel.  Modes:
     - "alltoall"  (p | m): one all_to_all routes whole blocks to
       devices, local batched bottom-up doubling inversion, one
       all_to_all routes the transposed-face pieces back.  This is the
       TPU-native adaptation of the paper's subgrid scheme; it needs 2
       collectives instead of O(log^2 p) (a beyond-paper latency win,
       possible exactly when there are at least p diagonal blocks).
     - "doubling"  (m < p): the SPMD equivalent of the paper's
       r1 x r1 x r2 subgrid inversions — repro.core.tri_inv's batched
       bottom-up doubling restricted to the diagonal n0-blocks, with
       all p processors cooperating on all blocks (S = O(log^2 p), the
       paper's Sec. V cost).  Faces are then formed by one transpose +
       one allgather over z.
     - "allgather" (fallback, any m): every device gathers all diagonal
       blocks and inverts redundantly.  Correct but bandwidth-suboptimal
       (W = n*n0 instead of ~n0^2); used only for odd divisibility.
2. *Sweep* (solve + update, paper Alg. It-Inv-TRSM lines 3-10): for each
   block i:  X_i = psum_x(L~[y,x](S_i,S_i) @ B[x,z](S_i))  — a GEMM by
   the pre-inverted block replaces the latency/VPU-bound substitution —
   then the trailing update B -= psum_y(panel @ X_i) with the panel
   reconstructed by an allgather over z (the paper's bcast, line 6).

The collectives per iteration match the paper exactly: one allreduce
over x (solve), one bcast over z (panel), one allreduce over y (update).
All collectives go through repro.core.comm, so tracing the program
yields the critical-path S/W/F that Sec. VII derives (the fori_loop body
is recorded once and multiplied by the trip count via comm.scope).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat

from repro.core import blocked, comm
from repro.core import grid as gridlib
from repro.core import tri_inv as ti
from repro.core.grid import TrsmGrid, check_divisibility
from repro.core.precision import gemm_precision

MESH_AXES = ("x", "y", "z")


def _piece_for(binv: jnp.ndarray, row_off, col_off, p1: int) -> jnp.ndarray:
    """Select the cyclic piece binv[:, row_off::p1, col_off::p1]
    with traced offsets."""
    return gridlib.block_piece(binv, row_off, col_off, p1, p1)


def _pieces_all_dests(binv: jnp.ndarray, p1: int, p2: int) -> jnp.ndarray:
    """For every destination (xd, yd, zd) build the transposed-face piece
    (rows ≡ yd, cols ≡ xd) of each local block: -> (p, mb, a, a)."""
    mb, n0, _ = binv.shape
    a = n0 // p1
    R = gridlib.block_pieces(binv, p1, p1)         # (roff, coff, i, l, c)
    R = jnp.swapaxes(R, 0, 1)                      # (coff=xd, roff=yd, ...)
    R = jnp.broadcast_to(R[:, :, None], (p1, p1, p2, mb, a, a))
    return R.reshape(p1 * p1 * p2, mb, a, a)


def _swap_perm(p1: int):
    return [(x * p1 + y, y * p1 + x) for x in range(p1) for y in range(p1)]


def _invert_diag_blocks(Lloc, *, n, n0, p1, p2, block_inv, mode,
                        accum_dtype=None, overlap=False):
    """Phase 1: return Dt (m, n0/p1, n0/p1) — the transposed-face pieces
    (rows ≡ y, cols ≡ x) of the inverted diagonal blocks.

    When ``accum_dtype`` is wider than the operand dtype the block
    inversion itself runs at the accumulate precision (cast up, invert,
    cast back): the inverse re-enters the sweep as a GEMM operand at
    compute precision, but its entries are formed at full accuracy —
    the same contract as ``preferred_element_type`` on the MXU."""
    if accum_dtype is not None and jnp.dtype(accum_dtype) != Lloc.dtype:
        inner, ldt = block_inv, Lloc.dtype
        block_inv = lambda b: inner(b.astype(accum_dtype)).astype(ldt)
    m = n // n0
    p = p1 * p1 * p2
    a = n0 // p1
    b = n0 // (p1 * p2)
    xi = comm.axis_index("x")
    yi = comm.axis_index("y")

    D = blocked.diag_blocks(Lloc, a, b)                # (m, a, b) local tiles

    if mode == "alltoall":
        assert m % p == 0, (m, p)
        mb = m // p
        # route: device f receives the pieces of blocks [f*mb, (f+1)*mb)
        Dr = comm.all_to_all(D, MESH_AXES, split_axis=0, concat_axis=0,
                             tiled=True)            # (p*mb, a, b)
        Dr = Dr.reshape(p, mb, a, b)
        blocks = ti._assemble_blocks(Dr, p1, p2)       # (mb, n0, n0)
        binv = block_inv(blocks)
        S = _pieces_all_dests(binv, p1, p2)            # (p, mb, a, a)
        Dt = comm.all_to_all(S.reshape(p * mb, a, a), MESH_AXES,
                             split_axis=0, concat_axis=0, tiled=True)
        return Dt                                      # (m, a, a), block order
    elif mode == "doubling":
        # cooperative inversion of the diagonal blocks (the SPMD
        # equivalent of the paper's subgrid RecTriInv), then form the
        # transposed faces: swap x<->y, gather cols over z, realign.
        Linv = ti.block_diag_inv_shard(Lloc, n=n, n0=n0, p1=p1, p2=p2,
                                       block_inv=block_inv)
        Dd = blocked.diag_blocks(Linv, a, b)           # (m, a, b) cyclic
        if p1 > 1:
            if overlap:
                # start/finish split: the face exchange is in flight
                # while XLA schedules any independent work between the
                # two (the fused overlapped solve issues panel 0's
                # gather before phase 1, so on an async backend the
                # whole inversion — this ppermute included — hides
                # behind the first panel's collective)
                Dd = comm.ppermute_finish(
                    comm.ppermute_start(Dd, ("x", "y"), _swap_perm(p1)))
            else:
                Dd = comm.ppermute(Dd, ("x", "y"), _swap_perm(p1))
        if p2 > 1:
            Dg = comm.all_gather(Dd, "z", axis=2, tiled=True)  # (m,a,p2*b)
            Dg = Dg.reshape(m, a, p2, b).transpose(0, 1, 3, 2)
            Dd = Dg.reshape(m, a, b * p2)
        return Dd                                      # (m, a, a)
    elif mode == "allgather":
        Dg = comm.all_gather(D, MESH_AXES, axis=0, tiled=False)
        blocks = ti._assemble_blocks(Dg, p1, p2)       # (m, n0, n0)
        binv = block_inv(blocks)
        return _piece_for(binv, yi, xi, p1)            # (m, a, a)
    raise ValueError(mode)


def _sweep_shard(Lloc, Dt, Bloc, *, n, k, n0, p1, p2,
                 accum_dtype=None, unroll=False, spans=None,
                 overlap=False, prefetched0=None):
    """Phase 2 (sweep, paper Alg. It-Inv-TRSM lines 3-10) against
    ALREADY-INVERTED diagonal faces Dt (m, n0/p1, n0/p1).

    Split out of the fused solve so a factor bank can hoist phase 1 to
    admission time (the factor is immutable, so re-inverting its
    diagonal blocks every solve is pure steady-state waste) and serve
    with this sweep alone.  ``unroll`` unrolls the m-trip loop at trace
    time — the banked programs use it so XLA sees straight-line batched
    GEMMs instead of a loop of dynamic slices.

    ``spans`` turns the unrolled sweep LEVEL-SCHEDULED (DESIGN.md
    Sec. 14): one admission-time-computed ``(lo, hi)`` dependent-block
    range (or None) per source column, from
    ``repro.core.structure.analyze``.  The cyclic layout keeps every
    global block row on a CONTIGUOUS local row range (``n0 % p1 == 0``
    — global row ``g`` lives at local row ``g // p1``), so the
    trailing update of column i statically narrows to the local rows
    of blocks [lo, hi): the panel is row-sliced BEFORE the z-allgather
    (less W, not just fewer flops) and a column with no off-diagonal
    nonzero block skips its update — and its two collectives —
    entirely.  Admission masks the factor to the block structure, so
    any non-dependent block row inside a conservative span multiplies
    exact zeros.  Trace-time decisions only: requires ``unroll``.

    ``overlap`` SOFTWARE-PIPELINES the panel collective (DESIGN.md
    Sec. 16): column i's panel depends only on Lloc (never on the
    solve chain), so its z-allgather is STARTED one step early —
    before column i-1's update GEMM + y-allreduce execute — and
    FINISHED where the update consumes it.  The ops and operands are
    identical to the sequential sweep (same slices, gathers, dots,
    reductions), only the issue order changes, so the result is
    bit-identical; level-scheduled skipped spans also skip the
    prefetch (the prefetch chain walks the live columns only).  The
    ``fori_loop`` form carries the FINISHED panel instead (a loop
    iteration is a barrier, so an unfinished handle cannot cross it):
    a prologue gathers panel 0 and the body prefetches panel i+1 with
    a clamped slice — one extra (discarded) gather on the last trip,
    so traced cost records m+1 panel gathers instead of m.
    ``prefetched0`` lets the fused solve start panel 0's gather BEFORE
    phase 1, hiding the whole diagonal inversion (its ppermute
    included) behind the first panel collective."""
    m = n // n0
    nl = n // p1
    kl = k // p2
    a = n0 // p1
    b = n0 // (p1 * p2)
    xi = comm.axis_index("x")
    ct = Bloc.dtype
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None else ct

    row_g = jnp.arange(nl) * p1 + xi                   # global row ids

    def _panel_start(i):
        """Issue column i's panel z-allgather (reads only Lloc; ``i``
        may be traced — dynamic_slice clamps an out-of-bounds start,
        which makes the fori path's last-trip prefetch harmless)."""
        if spans is not None:
            lo, hi = spans[i]
            rl, rows = lo * a, (hi - lo) * a
            panel = jax.lax.slice(Lloc, (rl, i * b),
                                  (rl + rows, (i + 1) * b))
        else:
            panel = jax.lax.dynamic_slice(Lloc, (0, i * b), (nl, b))
        return comm.all_gather_start(panel, "z", axis=0, tiled=False)

    def solve_step(i, Bcur, Xacc):
        Bi = jax.lax.dynamic_slice(Bcur, (i * a, 0), (a, kl))
        Dti = jax.lax.dynamic_index_in_dim(Dt, i, axis=0, keepdims=False)
        # solve via GEMM (l. 4-5); partials and the cross-x reduction
        # accumulate at acc (preferred_element_type on the MXU), the
        # carried values stay at compute precision.
        Xi = comm.psum(jax.lax.dot(Dti, Bi,
                                   precision=gemm_precision(Dti, Bi),
                                   preferred_element_type=acc),
                       "x").astype(ct)
        return Xi, jax.lax.dynamic_update_slice(Xacc, Xi, (i * a, 0))

    def apply_update(i, Bcur, Xi, pg):
        if spans is not None:
            # level-scheduled path: static row-span update.  lo >= i+1
            # always, so every span row is strictly below block i and
            # the row_g mask of the dense path is vacuous here.
            lo, hi = spans[i]
            rl, rows = lo * a, (hi - lo) * a
            pg = jnp.transpose(pg, (1, 2, 0)).reshape(rows, a)
            upd = comm.psum(
                jax.lax.dot(pg, Xi, precision=gemm_precision(pg, Xi),
                            preferred_element_type=acc),
                "y").astype(ct)
            Bspan = jax.lax.slice(Bcur, (rl, 0), (rl + rows, kl))
            return jax.lax.dynamic_update_slice(Bcur, Bspan - upd,
                                                (rl, 0))
        pg = jnp.transpose(pg, (1, 2, 0)).reshape(nl, a)  # cols t'=c*p2+z
        upd = comm.psum(jax.lax.dot(pg, Xi,
                                    precision=gemm_precision(pg, Xi),
                                    preferred_element_type=acc),
                        "y").astype(ct)                # update (lines 7-8)
        mask = (row_g >= (i + 1) * n0).astype(ct)[:, None]
        return Bcur - mask * upd

    def body(i, carry, update=True):
        Bcur, Xacc = carry
        Xi, Xacc = solve_step(i, Bcur, Xacc)
        if not update:
            return Bcur, Xacc
        pg = comm.all_gather_finish(_panel_start(i))
        return apply_update(i, Bcur, Xi, pg), Xacc

    def live_update(i):
        # the final trailing update only touches the discarded
        # remainder of B; unrolling lets us drop it entirely —
        # and a level schedule drops every dependent-free column
        return i + 1 < m and (spans is None or spans[i] is not None)

    x0 = compat.pcast_varying(jnp.zeros((nl, kl), Bloc.dtype), ("y", "z"))
    if unroll:
        if overlap:
            # double-buffered: the prefetch chain walks the LIVE
            # columns (skipped spans skip the prefetch too); each live
            # column's gather is started exactly once — same collective
            # count and operands as the sequential unroll.
            live = [i for i in range(m) if live_update(i)]
            succ = {live[t]: live[t + 1] for t in range(len(live) - 1)}
            pending = None
            if live:
                pending = prefetched0 if prefetched0 is not None \
                    else _panel_start(live[0])
            carry = (Bloc, x0)
            for i in range(m):
                Bcur, Xacc = carry
                Xi, Xacc = solve_step(i, Bcur, Xacc)
                if live_update(i):
                    pg = comm.all_gather_finish(pending)
                    # issue the next live column's gather BEFORE this
                    # update's GEMM + y-allreduce consume this one
                    pending = _panel_start(succ[i]) if i in succ else None
                    Bcur = apply_update(i, Bcur, Xi, pg)
                carry = (Bcur, Xacc)
            return carry[1]
        carry = (Bloc, x0)
        for i in range(m):
            carry = body(i, carry, update=live_update(i))
        return carry[1]
    assert spans is None, "level-scheduled sweep requires unroll"
    if overlap:
        # fori form: a loop iteration is a barrier, so carry the
        # FINISHED gathered panel; the prologue gather runs outside
        # the x m cost scope (hence m+1 recorded panel gathers).
        pg0 = comm.all_gather_finish(
            prefetched0 if prefetched0 is not None else _panel_start(0))

        def body_ov(i, carry):
            Bcur, Xacc, pg = carry
            Xi, Xacc = solve_step(i, Bcur, Xacc)
            nxt = _panel_start(i + 1)      # clamped no-op on last trip
            Bcur = apply_update(i, Bcur, Xi, pg)
            return Bcur, Xacc, comm.all_gather_finish(nxt)

        with comm.scope(m):
            _, X, _ = jax.lax.fori_loop(0, m, body_ov, (Bloc, x0, pg0))
        return X
    with comm.scope(m):
        _, X = jax.lax.fori_loop(0, m, body, (Bloc, x0))
    return X


def _it_inv_trsm_shard(Lloc, Bloc, *, n, k, n0, p1, p2, block_inv, mode,
                       accum_dtype=None, overlap=False):
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None \
        else Bloc.dtype
    pre0 = None
    if overlap:
        # start panel 0's z-allgather BEFORE phase 1: the panel reads
        # only Lloc, so the whole diagonal inversion (its collectives
        # included) can hide behind the first panel collective
        nl, b = n // p1, n0 // (p1 * p2)
        panel0 = jax.lax.dynamic_slice(Lloc, (0, 0), (nl, b))
        pre0 = comm.all_gather_start(panel0, "z", axis=0, tiled=False)
    Dt = _invert_diag_blocks(Lloc, n=n, n0=n0, p1=p1, p2=p2,
                             block_inv=block_inv, mode=mode,
                             accum_dtype=acc, overlap=overlap)
    return _sweep_shard(Lloc, Dt, Bloc, n=n, k=k, n0=n0, p1=p1, p2=p2,
                        accum_dtype=acc, overlap=overlap,
                        prefetched0=pre0)


# Sharding of the inverted-diagonal-faces array Dt (m, n0, n0): rows
# cyclic over y, cols cyclic over x (the transposed face the solve GEMM
# consumes), replicated over z — permuted storage like everything else.
SPEC_DT = P(None, "y", "x")


def dt_shape(n: int, n0: int) -> tuple:
    """Global logical shape of the phase-1 output Dt under
    :data:`SPEC_DT`: one (n0, n0) inverted face per diagonal block.
    Used by capacity-allocated factor banks to preallocate the
    resident Dt stack a replace scatters into (DESIGN.md Sec. 11)."""
    return (n // n0, n0, n0)


def it_inv_phase1_sharded(grid: TrsmGrid, n: int, n0: int,
                          block_inv: Callable | None = None,
                          mode: str | None = None, accum_dtype=None):
    """Build the (un-jitted) shard_map program for phase 1 ALONE:
    L_cyc (n, n) P("x", ("z","y")) -> Dt (m, n0, n0) :data:`SPEC_DT`,
    the transposed-face pieces of the inverted diagonal blocks.

    This is the factor-bank admission path (DESIGN.md Sec. 9): a
    resident factor is immutable, so its diagonal-block inversion runs
    ONCE here and the steady state runs :func:`it_inv_sweep_sharded`
    against the resident Dt — the per-solve cost drops the inversion
    term, which is also why the bank's tuned n0 is larger
    (``tuning.serving_n0``)."""
    mode = mode or pick_phase1_mode(n, n0, grid)
    binv = block_inv if block_inv is not None else blocked.tri_inv_batched
    body = functools.partial(_invert_diag_blocks, n=n, n0=n0,
                             p1=grid.p1, p2=grid.p2, block_inv=binv,
                             mode=mode, accum_dtype=accum_dtype)
    # SPEC_DT claims Dt is replicated over z.  It is, in every mode, but
    # the vma checker cannot prove it because none of the collectives
    # involved yields a z-invariant type:
    #   alltoall  - _pieces_all_dests sends the same piece to every
    #               z-destination (a broadcast over z before the tiled
    #               all_to_all), so devices differing only in z receive
    #               identical pieces from every source;
    #   doubling  - the faces are all-gathered over z (or p2 == 1);
    #   allgather - every device gathers every block and selects its
    #               piece by (y, x) alone.
    # So the check is off for this one program;
    # repro.core.selfcheck's "phase1_z" case asserts Dt bit-equal
    # across z for every mode.
    return compat.shard_map(body, mesh=grid.mesh,
                            in_specs=(grid.spec_L(),),
                            out_specs=SPEC_DT, check_vma=False)


def it_inv_sweep_sharded(grid: TrsmGrid, n: int, k: int, n0: int,
                         accum_dtype=None, unroll: bool = True,
                         structure=None, overlap: bool = False):
    """Build the (un-jitted) shard_map program for the SWEEP against
    pre-inverted diagonal faces: (L_cyc, Dt, B_cyc) -> X_cyc.

    Layouts as :func:`it_inv_trsm_sharded` plus Dt per :data:`SPEC_DT`
    (an :func:`it_inv_phase1_sharded` output).  Mode-independent: the
    phase-1 scheme only matters when Dt is produced.

    ``structure`` (a non-dense
    :class:`~repro.core.structure.FactorStructure`) compiles the
    LEVEL-SCHEDULED sweep instead: the admission-time analysis's
    per-column update spans are baked in as static slice bounds, zero
    blocks are skipped at trace time, and the loop is force-unrolled
    (skip decisions need a trace-time i).  Dense/None compiles the
    byte-identical program this function always built.

    ``overlap`` compiles the DOUBLE-BUFFERED sweep (DESIGN.md Sec. 16):
    panel i+1's z-allgather is started before panel i's update
    executes — bit-identical output (same ops, different issue order),
    structure-aware (skipped spans skip the prefetch)."""
    check_divisibility(n, k, n0, grid)
    spans = None
    if structure is not None and not structure.is_dense:
        from repro.core.structure import analyze
        spans = analyze(structure, n, n0).spans
        unroll = True
    body = functools.partial(_sweep_shard, n=n, k=k, n0=n0,
                             p1=grid.p1, p2=grid.p2,
                             accum_dtype=accum_dtype, unroll=unroll,
                             spans=spans, overlap=overlap)
    return compat.shard_map(body, mesh=grid.mesh,
                            in_specs=(grid.spec_L(), SPEC_DT,
                                      grid.spec_B()),
                            out_specs=grid.spec_X())


def pick_phase1_mode(n: int, n0: int, grid: TrsmGrid) -> str:
    m = n // n0
    p = grid.p
    if m % p == 0:
        return "alltoall"
    s0 = min(ti.pick_s0(n, grid.p1, grid.p2), n0)
    feasible = (s0 % (grid.p1 * grid.p2) == 0 and n0 % s0 == 0
                and (n0 // s0) & (n0 // s0 - 1) == 0)
    return "doubling" if feasible else "allgather"


def it_inv_trsm_sharded(grid: TrsmGrid, n: int, k: int, n0: int,
                        block_inv: Callable | None = None,
                        mode: str | None = None, accum_dtype=None,
                        overlap: bool = False):
    """Build the (un-jitted) shard_map program for fixed shapes, for
    composition inside larger jitted pipelines (repro.core.session).

    Takes/returns *cyclic storage* arrays (see repro.core.grid):
      L_cyc: (n, n) P("x", ("z","y"));  B_cyc: (n, k) P("x", "z")
      returns X_cyc: (n, k) P("y", "z") (rows cyclic over y).

    ``accum_dtype``: GEMM accumulation precision for the sweep (and the
    phase-1 block inversions); defaults to the operand dtype.  With
    bf16 operands pass float32 so the MXU accumulates at full width.

    ``overlap`` software-pipelines the sweep's panel collective and
    starts panel 0's gather before phase 1 (DESIGN.md Sec. 16); the
    output stays bit-identical to the sequential program.
    """
    check_divisibility(n, k, n0, grid)
    mode = mode or pick_phase1_mode(n, n0, grid)
    if mode == "alltoall" and (n // n0) % grid.p != 0:
        mode = pick_phase1_mode(n, n0, grid)
    binv = block_inv if block_inv is not None else blocked.tri_inv_batched

    body = functools.partial(_it_inv_trsm_shard, n=n, k=k, n0=n0,
                             p1=grid.p1, p2=grid.p2, block_inv=binv,
                             mode=mode, accum_dtype=accum_dtype,
                             overlap=overlap)
    # A Pallas kernel hook run in interpret mode (every non-TPU backend)
    # discharges into dynamic_slices whose index operands carry no
    # varying axes, which the vma checker rejects; the check is off
    # only when such a hook is plugged in.
    check = block_inv is None
    return compat.shard_map(body, mesh=grid.mesh,
                         in_specs=(grid.spec_L(), grid.spec_B()),
                         out_specs=grid.spec_X(), check_vma=check)


def it_inv_trsm_fn(grid: TrsmGrid, n: int, k: int, n0: int, dtype,
                   block_inv: Callable | None = None,
                   mode: str | None = None):
    """Jitted distributed solver for fixed shapes (cyclic storage)."""
    return jax.jit(it_inv_trsm_sharded(grid, n, k, n0,
                                       block_inv=block_inv, mode=mode))


def solve(L, B, grid: TrsmGrid, n0: int, *, block_inv=None,
          mode: str | None = None):
    """Convenience end-to-end solve: natural-layout L, B in; X out.

    Device-resident: routes through the compiled-solver cache via a
    :class:`repro.core.solver.SolveSpec`, so the cyclic permutations
    run as on-device gathers and repeated same-shape calls reuse the
    compiled program."""
    from repro.core import precision as preclib
    from repro.core.solver import SolveSpec, solver_for
    spec = SolveSpec(n=B.shape[0], k=B.shape[1], grid=grid,
                     policy=preclib.resolve(None, jnp.result_type(L)),
                     method="inv", n0=n0, mode=mode,
                     block_inv=block_inv)
    prog = solver_for(spec)
    return prog.solve(prog.prep(L), B)
