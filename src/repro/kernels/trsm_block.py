"""Pallas TPU kernel: base-case TRSM by forward substitution.

This kernel is deliberately the thing the paper REPLACES: a
row-sequential triangular solve.  On TPU the substitution recurrence
x_r = (b_r - L[r,:] X) / L[r,r] serializes on the VPU (no MXU work at
all) — which is exactly why It-Inv-TRSM's swap of base-case solves for
multiplications by pre-inverted blocks is a bigger win on TPU than on
the paper's MPI machine (DESIGN.md Sec. 2).  We keep it as (a) the
baseline for benchmarks/bench_gemm_fraction.py, which quantifies the
MXU-eligible flop share with and without inversion, and (b) a fallback
for non-power-of-two blocks.

Grid: (batch, column tiles).  The (n0, n0) L tile and an (n0, bn) X
tile live in VMEM; the row loop is a lax.fori_loop that reads and
writes one row of the refs per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.precision import gemm_precision


def _trsm_kernel(l_ref, b_ref, x_ref, *, accum_dtype):
    n0 = l_ref.shape[1]
    dt = x_ref.dtype
    hp = gemm_precision(l_ref.dtype, dt)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n0), 1)
    x_ref[0] = jnp.zeros(x_ref.shape[1:], dt)

    def body(r, carry):
        # row r of L and B read from the refs (a dynamic sublane
        # offset, which Mosaic takes — a traced index into a loaded
        # value is a dynamic_slice it does not); rows >= r of X are
        # still zero, so the full-length dot only sums solved rows.
        # One VPU row op per r — the serial baseline.  The row dot and
        # the subtraction run at accum_dtype so a low-precision
        # recurrence does not compound rounding row by row; the
        # stored X stays at the operand dtype.
        lr = l_ref[0, pl.ds(r, 1), :]                       # (1, n0)
        d = jax.lax.dot(lr, x_ref[0], precision=hp,
                        preferred_element_type=accum_dtype)  # (1, bn)
        lrr = jnp.sum(jnp.where(lane == r, lr, jnp.zeros_like(lr)),
                      axis=1, keepdims=True).astype(accum_dtype)
        br = b_ref[0, pl.ds(r, 1), :].astype(accum_dtype)
        x_ref[0, pl.ds(r, 1), :] = ((br - d) / lrr).astype(dt)
        return carry

    jax.lax.fori_loop(0, n0, body, 0)


def _trsm_valid_kernel(v_ref, l_ref, b_ref, x_ref, *, accum_dtype):
    """Validity-gated variant: a stack entry flagged 0 skips the whole
    substitution recurrence and writes zeros (its L is never read, so
    an arbitrary/zero diagonal cannot divide)."""
    v = v_ref[pl.program_id(0), 0]

    @pl.when(v != 0)
    def _solve():
        _trsm_kernel(l_ref, b_ref, x_ref, accum_dtype=accum_dtype)

    @pl.when(v == 0)
    def _skip():
        x_ref[0] = jnp.zeros_like(x_ref[0])


def trsm_substitution(L: jnp.ndarray, B: jnp.ndarray, *, bn: int = 128,
                      accum_dtype=jnp.float32,
                      interpret: bool = False, valid=None) -> jnp.ndarray:
    """Solve tril(L) X = B by in-kernel forward substitution.

    L: (m, n0, n0) batched or (n0, n0); B matching (m, n0, k)/(n0, k).
    ``accum_dtype``: precision of the per-row dot/update recurrence
    (float32 by default; the carried solution stays at B's dtype).
    ``valid``: optional (m,) mask — entries flagged 0 (blocks outside
    a :class:`~repro.core.structure.FactorStructure` schedule) skip
    the recurrence and write zeros; ``None`` compiles the exact
    unconditional kernel."""
    squeeze = L.ndim == 2
    if squeeze:
        L, B = L[None], B[None]
    m, n0, _ = L.shape
    _, _, k = B.shape
    bn = min(bn, k)
    assert k % bn == 0, (k, bn)

    l_spec = pl.BlockSpec((1, n0, n0), lambda b, j: (b, 0, 0))
    b_spec = pl.BlockSpec((1, n0, bn), lambda b, j: (b, 0, j))
    if valid is None:
        out = pl.pallas_call(
            functools.partial(_trsm_kernel,
                              accum_dtype=jnp.dtype(accum_dtype)),
            grid=(m, k // bn),
            in_specs=[l_spec, b_spec],
            out_specs=b_spec,
            out_shape=compat.out_struct_like((m, n0, k), B.dtype, B),
            interpret=interpret,
        )(L, B)
        return out[0] if squeeze else out
    v = jnp.asarray(valid, jnp.int32).reshape(m, 1)
    out = pl.pallas_call(
        functools.partial(_trsm_valid_kernel,
                          accum_dtype=jnp.dtype(accum_dtype)),
        grid=(m, k // bn),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),   # whole mask
                  l_spec, b_spec],
        out_specs=b_spec,
        out_shape=compat.out_struct_like((m, n0, k), B.dtype, B),
        interpret=interpret,
    )(v, L, B)
    return out[0] if squeeze else out
