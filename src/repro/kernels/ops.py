"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute under interpret=True —
the kernel body runs in Python per grid step, validating the exact TPU
program.  On TPU the same calls compile to Mosaic.  ``block_inv_kernel``
is the drop-in hook for the distributed solvers' ``block_inv=``
parameter (repro.core.inv_trsm / tri_inv).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import trmm as _trmm
from repro.kernels import tri_inv_block as _tib
from repro.kernels import trsm_block as _tsb
from repro.kernels import ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("bt", "bn", "accum_dtype"))
def trmm(L, X, bt: int = 128, bn: int = 128, accum_dtype=jnp.float32,
         block_mask=None):
    """C = tril(L) @ X (structure-skipping tiled MXU kernel).

    ``accum_dtype`` is the MXU accumulation width (scratch +
    preferred_element_type); float32 by default so bf16 operands
    accumulate at full precision.  ``block_mask`` (optional
    (n/bt, n/bt) validity tiles, e.g. ``FactorStructure.block_mask``)
    skips zero tiles on top of the above-diagonal skip."""
    return _trmm.trmm(L, X, bt=bt, bn=bn, accum_dtype=accum_dtype,
                      interpret=_interpret(), block_mask=block_mask)


@functools.partial(jax.jit, static_argnames=("accum_dtype",))
def tri_inv_blocks(Ls, accum_dtype=jnp.float32, valid=None):
    """Batched lower-triangular inversion (doubling, in-VMEM); level
    GEMMs accumulate at ``accum_dtype``.  ``valid`` (optional (m,)
    mask) writes zeros for flagged-out stack entries instead of
    inverting them."""
    return _tib.tri_inv_blocks(Ls, accum_dtype=accum_dtype,
                               interpret=_interpret(), valid=valid)


@functools.partial(jax.jit, static_argnames=("bn", "accum_dtype"))
def trsm_substitution(L, B, bn: int = 128, accum_dtype=jnp.float32,
                      valid=None):
    """Baseline substitution TRSM (VPU-serial; what the paper replaces).
    The row recurrence runs at ``accum_dtype``.  ``valid`` (optional
    (m,) mask) skips flagged-out stack entries, writing zeros."""
    return _tsb.trsm_substitution(L, B, bn=bn, accum_dtype=accum_dtype,
                                  interpret=_interpret(), valid=valid)


def block_inv_kernel(blocks: jnp.ndarray) -> jnp.ndarray:
    """Hook matching the ``block_inv`` signature of the distributed
    solvers: (m, n0, n0) -> batched inverses by the Pallas kernel.

    Blocks the kernel does not take are rejected eagerly, never
    rerouted to another inverter: a zero-sized batch or a 0x0 /
    non-square block would otherwise flow into the Pallas grid with a
    0-extent dimension and fail deep inside Mosaic (or silently produce
    an empty program), and the doubling levels need a power-of-two
    block size.  Callers with other block sizes pass
    ``repro.core.blocked.tri_inv_batched`` instead."""
    if blocks.ndim != 3:
        raise ValueError(
            f"block_inv_kernel expects a (m, n0, n0) stack of blocks, "
            f"got ndim={blocks.ndim} shape={blocks.shape}")
    m, r, n0 = blocks.shape
    if r != n0:
        raise ValueError(
            f"diagonal blocks must be square, got {r}x{n0} "
            f"(shape={blocks.shape})")
    if m == 0 or n0 == 0:
        raise ValueError(
            f"degenerate block batch {blocks.shape}: zero-sized batches "
            f"cannot be inverted — check n0 / grid divisibility upstream")
    if n0 & (n0 - 1):
        raise ValueError(
            f"block size {n0} is not a power of two: the Pallas inverter "
            f"takes power-of-two blocks only (use "
            f"repro.core.blocked.tri_inv_batched for this n0)")
    return _tib.tri_inv_blocks(blocks, interpret=_interpret())
