"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth that tests/test_kernels.py sweeps against
(shapes x dtypes, interpret=True execution of the kernels on CPU) and
that chip_smoke.py compares the compiled kernels with on the chip —
hence f32 matmuls at HIGHEST precision (a TPU's default is one bf16
pass)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def trmm_ref(L: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    """C = tril(L) @ X."""
    return jnp.matmul(jnp.tril(L), X, precision=jax.lax.Precision.HIGHEST)


def tri_inv_blocks_ref(Ls: jnp.ndarray) -> jnp.ndarray:
    """Batched lower-triangular inversion: (m, n0, n0) -> inverses."""
    n0 = Ls.shape[-1]
    eye = jnp.eye(n0, dtype=Ls.dtype)

    def one(L):
        return jax.scipy.linalg.solve_triangular(L, eye, lower=True)

    return jax.vmap(one)(Ls)


def trsm_ref(L: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """X with tril(L) X = B."""
    return jax.scipy.linalg.solve_triangular(jnp.tril(L), B, lower=True)
