"""Pallas TPU kernel: triangular matrix-matrix multiply  C = tril(L) @ X.

This is the MXU workhorse of It-Inv-TRSM: both the solve step (multiply
by the inverted diagonal block) and the trailing update (off-diagonal
panel times X_i) are triangular-structured GEMMs.  The kernel exploits
the structure by *skipping* every (row-tile, k-tile) pair above the
diagonal — for an n x n triangular operand that halves the compute and
the HBM->VMEM traffic relative to a dense GEMM.

Tiling: square (bt x bt) L tiles so the zero/nonzero tile test is exact
(tile (i, kk) is identically zero iff kk > i); X and C tiles are
(bt x bn).  The k-loop is the innermost grid dimension; a VMEM scratch
accumulator carries partial sums in f32 regardless of operand dtype
(MXU-native mixed precision), and tiles with kk > i are skipped with
pl.when, so the dominant loop issues one MXU matmul per visited tile.

Block shapes default to (128, 128): MXU-aligned (the systolic array is
128x128 after dtype packing) and three live tiles fit comfortably in
the ~16 MiB of VMEM up to bt = bn = 512.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.precision import gemm_precision


def _trmm_kernel(l_ref, x_ref, o_ref, acc_ref, *, nk: int, accum_dtype):
    i = pl.program_id(0)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kk <= i)          # tiles strictly above the diagonal are 0
    def _mac():
        acc_ref[...] += jnp.dot(l_ref[...], x_ref[...],
                                precision=gemm_precision(l_ref.dtype,
                                                         x_ref.dtype),
                                preferred_element_type=accum_dtype)

    @pl.when(kk == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _trmm_masked_kernel(m_ref, l_ref, x_ref, o_ref, acc_ref, *,
                        nk: int, accum_dtype):
    """The structure-skipping variant: the (ni, nk) validity mask sits
    whole in SMEM; a zero entry (i, kk) skips the MXU op exactly like
    the above-diagonal test (DESIGN.md Sec. 14)."""
    i = pl.program_id(0)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((kk <= i) & (m_ref[i, kk] != 0))
    def _mac():
        acc_ref[...] += jnp.dot(l_ref[...], x_ref[...],
                                precision=gemm_precision(l_ref.dtype,
                                                         x_ref.dtype),
                                preferred_element_type=accum_dtype)

    @pl.when(kk == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def trmm(L: jnp.ndarray, X: jnp.ndarray, *, bt: int = 128, bn: int = 128,
         accum_dtype=jnp.float32, interpret: bool = False,
         block_mask=None) -> jnp.ndarray:
    """C = tril(L) @ X with L: (n, n), X: (n, k).

    ``accum_dtype``: dtype of the VMEM scratch accumulator and the MXU
    partial sums (``preferred_element_type``).  Defaults to float32 —
    the MXU-native accumulation width for bf16/f32 inputs; pass the
    operand dtype to reproduce a narrow-accumulation GEMM exactly.

    ``block_mask``: optional (n/bt, n/bt) validity mask at TILE
    granularity (a ``FactorStructure.block_mask`` when bt == n0).  A
    zero tile skips the MXU op and its VMEM traffic on top of the
    above-diagonal skip; ``None`` (the default) compiles the exact
    dense-triangular kernel unchanged."""
    n, n2 = L.shape
    _, k = X.shape
    assert n == n2 and X.shape[0] == n, (L.shape, X.shape)
    accum_dtype = jnp.dtype(accum_dtype)
    bt = min(bt, n)
    bn = min(bn, k)
    assert n % bt == 0 and k % bn == 0, (n, k, bt, bn)
    ni, nj, nk = n // bt, k // bn, n // bt

    grid = (ni, nj, nk)
    # clamp the k-index for skipped tiles so we never prefetch
    # out of the triangle (the compute is pl.when-guarded).
    l_spec = pl.BlockSpec((bt, bt), lambda i, j, kk: (i, jnp.minimum(kk, i)))
    x_spec = pl.BlockSpec((bt, bn), lambda i, j, kk: (kk, j))
    o_spec = pl.BlockSpec((bt, bn), lambda i, j, kk: (i, j))
    if block_mask is None:
        return pl.pallas_call(
            functools.partial(_trmm_kernel, nk=nk,
                              accum_dtype=accum_dtype),
            grid=grid,
            in_specs=[l_spec, x_spec],
            out_specs=o_spec,
            out_shape=compat.out_struct_like((n, k), X.dtype, X),
            scratch_shapes=[pltpu.VMEM((bt, bn), accum_dtype)],
            interpret=interpret,
        )(L, X)
    mask = jnp.asarray(block_mask, jnp.int32)
    assert mask.shape == (ni, nk), (mask.shape, ni, nk)
    return pl.pallas_call(
        functools.partial(_trmm_masked_kernel, nk=nk,
                          accum_dtype=accum_dtype),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),   # whole mask
                  l_spec, x_spec],
        out_specs=o_spec,
        out_shape=compat.out_struct_like((n, k), X.dtype, X),
        scratch_shapes=[pltpu.VMEM((bt, bn), accum_dtype)],
        interpret=interpret,
    )(mask, L, X)
