"""Inputs made on the device from ``--seed``: factors and right-hand
sides.

Every value is a function of the seed and its position alone, so the
reference can make any rows of a factor again after the window without
a second resident copy.  Dense values come from integer hashing scaled
by a power of two: exact in float32 and free of transcendental
functions, so the same entry is bit-identical whichever program,
layout or sharding made it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256                  # rows per chunk the reference reads

# streams of one seed
FACTOR, RHS, KFAC = 1, 2, 3


def base_key(seed: int, stream: int):
    """A key for one stream of one seed; seeds are whole numbers of any
    size (the low 31 bits seed the key, the rest are folded in)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    rest = seed >> 31
    while rest:
        key = jax.random.fold_in(key, rest & 0xFFFFFFFF)
        rest >>= 32
    return jax.random.fold_in(key, stream)


def uniform(key, shape):
    """Uniform values k / 2**24 - 1/2 in [-1/2, 1/2), exact in float32
    (HPL-MxP draws its matrix entries from the same interval)."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    return (bits >> 8).astype(jnp.float32) * (2.0 ** -24) - 0.5


def seed_words(seed: int, stream: int):
    """Two uint32 words of (seed, stream) for the element hash."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    folded = 0
    while seed:
        folded = (folded * 0x9E3779B1 + (seed & 0xFFFFFFFF)) & 0xFFFFFFFF
        seed >>= 32
    return np.uint32(folded), np.uint32((stream * 0x85EBCA77) & 0xFFFFFFFF)


def _fmix(h):
    """murmur3's 32-bit finalizer (uint32 in, uint32 out)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def dense_entries(words, r, c, n: int):
    """Entry (r, c) of the order-n dense lower factor, for global row
    and column indices of any (broadcast) shape: a hash of (seed, r, c)
    mapped to [-1/2, 1/2) below the diagonal, n on it, 0 above, so the
    factor is diagonally dominant by rows.  An element hash, not a
    stream, so any layout of the factor can be made where it lives."""
    k0, k1 = words
    r = r.astype(jnp.uint32)
    c = c.astype(jnp.uint32)
    h = _fmix(_fmix(r ^ k0) + c * jnp.uint32(0x9E3779B1) + k1)
    u = (h >> 8).astype(jnp.float32) * (2.0 ** -24) - 0.5
    return jnp.where(c < r, u, jnp.where(c == r, jnp.float32(n), 0.0))


def cyclic_global(size: int, p: int):
    """Global index held at each storage position of the stride-p
    cyclic layout (storage block b, slot l holds b + l * p)."""
    s = jnp.arange(size, dtype=jnp.uint32)
    per = size // p
    return (s % per) * p + s // per


@functools.lru_cache(maxsize=None)
def dense_factor_program(n: int, p_row: int, p_col: int, sharding):
    """The jitted whole-factor generator: the factor in the cyclic
    storage of strides (p_row, p_col) (natural layout for 1, 1), made
    in place under ``sharding``."""
    def make(k0, k1):
        r = cyclic_global(n, p_row)[:, None]
        c = cyclic_global(n, p_col)[None, :]
        return dense_entries((k0, k1), r, c, n)
    return jax.jit(make, out_shardings=sharding)


def dense_factor(seed: int, n: int, sharding, p_row: int = 1,
                 p_col: int = 1):
    return dense_factor_program(n, p_row, p_col, sharding)(
        *seed_words(seed, FACTOR))


@functools.lru_cache(maxsize=None)
def _rows_program(n: int, count: int):
    def make(k0, k1, r0):
        r = r0.astype(jnp.uint32) + jnp.arange(count, dtype=jnp.uint32)
        return dense_entries((k0, k1), r[:, None],
                             jnp.arange(n, dtype=jnp.uint32)[None, :], n)
    return jax.jit(make)


def dense_factor_rows(seed: int, n: int, r0: int, count: int):
    """Natural rows ``r0 .. r0 + count - 1`` of the factor (one device),
    for the reference."""
    return _rows_program(n, count)(*seed_words(seed, FACTOR),
                                   jnp.int32(r0))


@functools.lru_cache(maxsize=None)
def _rhs_program(n: int, k: int, sharding):
    return jax.jit(lambda key, i: uniform(jax.random.fold_in(key, i),
                                          (n, k)),
                   out_shardings=sharding)


def rhs(seed: int, index: int, n: int, k: int, sharding=None):
    """Right-hand side number ``index``: (n, k) uniform values."""
    return _rhs_program(n, k, sharding)(base_key(seed, RHS),
                                        jnp.int32(index))


@functools.lru_cache(maxsize=None)
def _rhs_stack_program(count: int, n: int, k: int, sharding):
    return jax.jit(lambda key: jax.vmap(
        lambda i: uniform(jax.random.fold_in(key, i), (n, k)))(
            jnp.arange(count)), out_shardings=sharding)


def rhs_stack(seed: int, count: int, n: int, k: int, sharding=None,
              group: int = 0):
    """Right-hand sides 0 .. count-1 of stack ``group``: (count, n, k)."""
    return _rhs_stack_program(count, n, k, sharding)(
        jax.random.fold_in(base_key(seed, RHS), group))


@functools.lru_cache(maxsize=None)
def _kfac_program(n: int, tokens: int, damping: float, sharding):
    def make(key):
        # unit-variance activations of `tokens` rows; the Kronecker
        # factor is their second moment, damped, and we keep its
        # Cholesky factor (exactly lower: the solve's residual reads
        # the whole matrix)
        X = uniform(key, (tokens, n)) * jnp.sqrt(12.0)
        A = jnp.matmul(X.T, X, precision=jax.lax.Precision.HIGHEST) \
            / tokens
        # damping relative to the mean eigenvalue, as repro.optim does
        A = A + damping * (jnp.trace(A) / n) * jnp.eye(n, dtype=A.dtype)
        return jnp.tril(jnp.linalg.cholesky(A))
    return jax.jit(make, out_shardings=sharding)


def kfac_factor(seed: int, slot: int, version: int, n: int, tokens: int,
                damping: float, sharding=None):
    """Version ``version`` of the K-FAC factor held in ``slot``:
    chol(M + damping tr(M)/n I), M = X^T X / tokens, for activations X made from
    (seed, slot, version)."""
    key = jax.random.fold_in(jax.random.fold_in(
        base_key(seed, KFAC), slot), version)
    return _kfac_program(n, tokens, float(damping), sharding)(key)
