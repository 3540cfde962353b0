"""Pallas TPU kernel: batched inversion of small lower-triangular blocks.

This is the compute core of the paper's Diagonal-Inverter (Sec. VI-A):
after the all-to-all routes whole n0 x n0 diagonal blocks to devices,
each device inverts a *stack* of blocks.  The kernel runs the bottom-up
doubling scheme (Sec. V re-derived for SPMD, see repro.core.blocked)
entirely in VMEM:

    level s: for every diagonal 2s-block  [[A, 0], [B, C]]  (A, C already
    inverted) finalize the off-diagonal:  B' = -C^-1 B A^-1  — two MXU
    matmuls batched over all n0/(2s) sub-blocks.

All log2(n0) levels execute on one VMEM-resident tile, so the block is
read from HBM exactly once and written once — arithmetic intensity
n0/3 flops/byte at the HBM level, vs O(1) for row-by-row substitution.
The first level (1x1 diagonal) is a vectorized reciprocal on the VPU;
every other level is two full-tile MXU matmuls (masked operands, so
the tile never needs a gather or an unaligned slice).

Grid: one block per grid step (the stack dimension); block sizes up to
512 fit VMEM (a few n0^2 * 4B temporaries, well under 16 MiB).  n0
must be a power of two (the Diagonal-Inverter guarantees this by
construction).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.blocked import tri_inv_tile


def _tri_inv_kernel(l_ref, o_ref, *, accum_dtype):
    o_ref[0] = tri_inv_tile(l_ref[0], accum_dtype)


def _tri_inv_valid_kernel(v_ref, l_ref, o_ref, *, accum_dtype):
    """Validity-gated variant: an invalid stack entry (a block the
    structure's level schedule never touches) writes zeros instead of
    inverting — no division by its (arbitrary) diagonal."""
    v = v_ref[pl.program_id(0), 0]

    @pl.when(v != 0)
    def _inv():
        o_ref[0] = tri_inv_tile(l_ref[0], accum_dtype)

    @pl.when(v == 0)
    def _skip():
        o_ref[0] = jnp.zeros_like(o_ref[0])


def tri_inv_blocks(Ls: jnp.ndarray, *, accum_dtype=jnp.float32,
                   interpret: bool = False, valid=None):
    """Invert a stack (m, n0, n0) of lower-triangular blocks.

    ``accum_dtype``: accumulation width of the doubling-level GEMMs
    (float32 by default — full MXU accumulation for bf16 operands).

    ``valid``: optional (m,) validity mask — stack entries flagged 0
    (blocks a :class:`~repro.core.structure.FactorStructure` schedule
    never touches) are written as zeros instead of inverted, so their
    arbitrary diagonals never reach a reciprocal.  ``None`` (default)
    compiles the exact unconditional kernel."""
    m, n0, n02 = Ls.shape
    assert n0 == n02 and (n0 & (n0 - 1)) == 0, Ls.shape
    if valid is None:
        return pl.pallas_call(
            functools.partial(_tri_inv_kernel,
                              accum_dtype=jnp.dtype(accum_dtype)),
            grid=(m,),
            in_specs=[pl.BlockSpec((1, n0, n0), lambda b: (b, 0, 0))],
            out_specs=pl.BlockSpec((1, n0, n0), lambda b: (b, 0, 0)),
            out_shape=compat.out_struct_like((m, n0, n0), Ls.dtype, Ls),
            interpret=interpret,
        )(Ls)
    v = jnp.asarray(valid, jnp.int32).reshape(m, 1)
    return pl.pallas_call(
        functools.partial(_tri_inv_valid_kernel,
                          accum_dtype=jnp.dtype(accum_dtype)),
        grid=(m,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),   # whole mask
                  pl.BlockSpec((1, n0, n0), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, n0, n0), lambda b: (b, 0, 0)),
        out_shape=compat.out_struct_like((m, n0, n0), Ls.dtype, Ls),
        interpret=interpret,
    )(v, Ls)
