"""Mixed-precision pipeline: PrecisionPolicy resolution, the on-device
iterative-refinement loop (repro.core.refine), policy-aware cache keys,
and the kernels' explicit accumulate dtypes.

Single-device grid; the multi-device variants of the solve paths run in
repro.core.selfcheck (marked slow)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import grid as gridlib, precision, refine, session


@pytest.fixture(autouse=True, scope="module")
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="module")
def grid():
    return gridlib.make_trsm_mesh(1, 1)


def _mats(n=128, k=16, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    L = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    B = rng.standard_normal((n, k))
    return L.astype(dtype), B.astype(dtype)


def _relres(L, X, B):
    X = np.asarray(X, np.float64)
    return (np.linalg.norm(L.astype(np.float64) @ X - B)
            / np.linalg.norm(B))


# ----------------------------- the policy -----------------------------

def test_presets_have_expected_roles():
    p = precision.PRESETS["bf16_refine"]
    assert (p.storage, p.compute, p.accumulate, p.residual) == \
        ("bfloat16", "bfloat16", "float32", "float32")
    assert p.refine_steps == 2 and p.refines
    assert p.io_dtype == jnp.dtype("float32")
    # non-refining presets serve at the compute dtype
    assert precision.PRESETS["bf16"].io_dtype == jnp.dtype("bfloat16")
    assert precision.PRESETS["fp32"].io_dtype == jnp.dtype("float32")
    assert precision.PRESETS["fp64_refine"].io_dtype == \
        jnp.dtype("float64")


def test_resolve_accepts_name_policy_dtype():
    p = precision.resolve("bf16_refine")
    assert precision.resolve(p) is p
    legacy = precision.resolve(None, np.float64)
    assert legacy.storage == legacy.residual == "float64"
    assert not legacy.refines
    with pytest.raises(ValueError, match="unknown precision preset"):
        precision.resolve("fp8_dream")
    with pytest.raises(ValueError, match="precision= or dtype="):
        precision.resolve(None, None)
    with pytest.raises(ValueError, match="refine_steps"):
        precision.PrecisionPolicy(name="bad", storage="float32",
                                  compute="float32", accumulate="float32",
                                  residual="float32", refine_steps=-1)


def test_policies_are_distinct_cache_keys(grid):
    cache = session.CompiledSolverCache()
    for prec in ("fp32", "bf16", "bf16_refine"):
        session.get_solver(grid, n=32, k=4, n0=8, precision=prec,
                           cache=cache)
    assert len(cache) == 3 and cache.stats()["misses"] == 3
    # same preset again: a hit, not a rebuild
    session.get_solver(grid, n=32, k=4, n0=8, precision="bf16_refine",
                       cache=cache)
    assert cache.stats()["hits"] == 1
    # the cosmetic name is NOT part of the key: the legacy uniform
    # float32 policy and the "fp32" preset share one compiled program
    assert precision.resolve(None, np.float32) == \
        precision.PRESETS["fp32"]
    session.get_solver(grid, n=32, k=4, n0=8, dtype=np.float32,
                       cache=cache)
    assert cache.stats()["hits"] == 2 and len(cache) == 3


def test_fp64_policy_requires_x64(grid):
    jax.config.update("jax_enable_x64", False)
    try:
        with pytest.raises(ValueError, match="needs float64"):
            session.get_solver(grid, n=32, k=4, n0=8,
                               precision="fp64_refine")
    finally:
        jax.config.update("jax_enable_x64", True)


# ----------------------- the refinement operator -----------------------

_VARIANTS = [(True, False), (False, False), (True, True), (False, True)]
# (p1, p2, n, stack): the dense product behind stride-4 cyclic maps
# (ids kept as the variant alone), and the one-device row-blocked
# product with even and ragged blocks, on single and stacked operands
_OPERATOR_CASES = [
    pytest.param(lower, transpose, p1, p2, n, m,
                 id=f"{lower}-{transpose}" if p1 > 1 else
                 f"{lower}-{transpose}-1x1-n{n}-m{m}")
    for p1, p2, n, m in [(2, 2, 32, None), (1, 1, 32, None),
                         (1, 1, 37, None), (1, 1, 37, 3)]
    for lower, transpose in _VARIANTS]


@pytest.mark.parametrize("lower,transpose,p1,p2,n,m", _OPERATOR_CASES)
def test_apply_cyclic_operator_matches_dense(lower, transpose, p1, p2, n,
                                             m, monkeypatch):
    """op(A) @ X reconstructed from the RESIDENT cyclic factor must
    equal the dense product, for every operator reduction variant; on
    one device through the row-blocked product (blocks of 8 rows here,
    engaged by shrinking the block rule's alignment and minimum)."""
    monkeypatch.setattr(refine, "_ALIGN", 8)
    monkeypatch.setattr(refine, "_MIN_BLOCK_ROWS", 8)
    if p1 * p2 == 1:
        assert len(refine.residual_row_bounds(n)) > 1
    k = 5
    rng = np.random.default_rng(4)
    lead = () if m is None else (m,)
    L = np.tril(rng.standard_normal(lead + (n, n))) + np.eye(n)
    A = L if lower else np.swapaxes(L, -1, -2)
    op = np.swapaxes(A, -1, -2) if transpose else A
    X = rng.standard_normal(lead + (n, k))
    rev = lower == transpose
    L_cyc = gridlib.cyclic_matrix_device(
        jnp.asarray(A), p1, p1 * p2, reverse_rows=rev, reverse_cols=rev,
        transpose=transpose)
    got = refine.apply_cyclic_operator(L_cyc, jnp.asarray(X),
                                       p1=p1, p2=p2, reverse=rev)
    np.testing.assert_allclose(np.asarray(got), op @ X, atol=1e-10)


@pytest.mark.parametrize("n,bounds", [
    (32768, tuple(range(2048, 32769, 2048))),
    (10000, tuple(range(640, 9601, 640)) + (10000,)),  # ragged last block
    (8192, tuple(range(512, 8193, 512))),               # the smallest blocks
    (8000, tuple(range(512, 7681, 512)) + (8000,)),
    (5000, (5000,)),                       # blocks under 512 rows: dense
    (1, (1,)),
])
def test_residual_row_bounds(n, bounds):
    """Sixteen blocks of a whole number of 128 rows, the last ragged;
    the dense product where a block would be under 512 rows.  The tile
    share counts the multiply-adds the blocks do: (T+1)/(2T) for T
    equal blocks, 1 for the dense product and on every larger mesh."""
    assert refine.residual_row_bounds(n) == bounds
    starts = (0,) + bounds[:-1]
    assert all(e - s <= bounds[0] for s, e in zip(starts, bounds))
    share = sum((e - s) * e for s, e in zip(starts, bounds)) / n ** 2
    assert refine.residual_tile_share(n, 1, 1) == pytest.approx(share)
    assert refine.residual_tile_share(n, 2, 1) == 1.0
    if n == 32768:
        assert share == 17 / 32


@pytest.mark.parametrize("method", ["inv", "rec"])
def test_bf16_refine_recovers_fp32_accuracy(grid, method):
    """The acceptance bar: bf16_refine within 10x of the pure-fp32
    relative residual (same solve, same grid)."""
    L, B = _mats(n=256, k=16)
    X32 = core.trsm(L, B, grid, method=method, n0=32, precision="fp32")
    Xbf = core.trsm(L, B, grid, method=method, n0=32,
                    precision="bf16_refine")
    r32, rbf = _relres(L, X32, B), _relres(L, Xbf, B)
    assert rbf < 10 * r32, (r32, rbf)
    # and the unrefined bf16 sweep really is orders of magnitude worse
    # (i.e. the refinement is doing the work, not the test being loose)
    rraw = _relres(L, core.trsm(L, B, grid, method=method, n0=32,
                                precision="bf16"), B)
    assert rraw > 50 * rbf, (rraw, rbf)


def test_fp64_refine_exceeds_fp32_sweep_accuracy(grid):
    L, B = _mats(n=128, k=8, dtype=np.float64)
    X = core.trsm(L, B, grid, method="inv", n0=32,
                  precision="fp64_refine")
    assert X.dtype == jnp.dtype("float64")
    assert _relres(L, X, B) < 1e-12
    # the fp32 sweep alone cannot reach that
    assert _relres(L, core.trsm(L.astype(np.float32),
                                B.astype(np.float32), grid, method="inv",
                                n0=32, precision="fp32"), B) > 1e-9


def test_refine_steps_monotone(grid):
    """Each unrolled pass tightens the residual until it saturates."""
    L, B = _mats(n=128, k=8)
    res = []
    for steps in (0, 1, 2):
        pol = precision.PrecisionPolicy(
            name=f"bf16_r{steps}", storage="bfloat16", compute="bfloat16",
            accumulate="float32", residual="float32", refine_steps=steps)
        X = core.trsm(L, B, grid, method="inv", n0=32, precision=pol)
        res.append(_relres(L, X, B))
    assert res[1] < res[0] / 10, res
    assert res[2] <= res[1], res


def test_session_serves_refined_dtype_and_residual_copy(grid):
    L, _ = _mats(n=64, k=8)
    sess = core.TrsmSession(L, grid, method="inv", n0=16,
                            precision="bf16_refine")
    assert sess.dtype == jnp.dtype("float32")
    assert sess.factor_cyclic.dtype == jnp.dtype("bfloat16")
    assert sess.factor_cyclic_residual.dtype == jnp.dtype("float32")
    # non-refining session keeps a single resident copy
    sess32 = core.TrsmSession(L, grid, method="inv", n0=16,
                              precision="fp32")
    assert sess32.factor_cyclic_residual is None


def test_request_server_serves_bf16_refine():
    from repro.train import serve_step as ss
    n = 64
    rng = np.random.default_rng(5)
    L = (np.tril(rng.standard_normal((n, n)))
         + n * np.eye(n)).astype(np.float32)
    server = ss.make_trsm_server(L, panel_k=4, n0=16,
                                 precision="bf16_refine")
    reqs = [rng.standard_normal((n, w)).astype(np.float32)
            for w in (1, 3, 2)]
    for r in reqs:
        server.submit(r)
    outs = server.drain()
    for r, x in zip(reqs, outs):
        assert x.dtype == jnp.dtype("float32")
        assert _relres(L, x, r.astype(np.float64)) < 1e-5


# ------------------------ kernel accumulate dtypes ------------------------

def test_trmm_accum_dtype_controls_accuracy():
    from repro.kernels import ops, ref
    rng = np.random.default_rng(9)
    n, k = 256, 128
    L = jnp.asarray(np.tril(rng.standard_normal((n, n))), jnp.bfloat16)
    X = jnp.asarray(rng.standard_normal((n, k)), jnp.bfloat16)
    want = np.asarray(ref.trmm_ref(L.astype(jnp.float32),
                                   X.astype(jnp.float32)))
    got32 = np.asarray(ops.trmm(L, X, accum_dtype=jnp.float32), np.float32)
    gotbf = np.asarray(ops.trmm(L, X, accum_dtype=jnp.bfloat16), np.float32)
    err32 = np.abs(got32 - want).max()
    errbf = np.abs(gotbf - want).max()
    # fp32 accumulation of bf16 operands beats bf16 accumulation
    assert err32 < errbf, (err32, errbf)


def test_tri_inv_blocks_accum_dtype():
    from repro.kernels import ops
    rng = np.random.default_rng(3)
    n0 = 32
    Ls = np.tril(rng.standard_normal((4, n0, n0))) \
        + n0 * np.broadcast_to(np.eye(n0), (4, n0, n0))
    out = ops.tri_inv_blocks(jnp.asarray(Ls, jnp.float32),
                             accum_dtype=jnp.float32)
    prod = np.einsum("bij,bjk->bik", np.asarray(out), Ls)
    np.testing.assert_allclose(
        prod, np.broadcast_to(np.eye(n0), prod.shape), atol=1e-4)


def test_trsm_substitution_accum_dtype():
    from repro.kernels import ops, ref
    rng = np.random.default_rng(6)
    n0, k = 32, 32
    L = np.tril(rng.standard_normal((n0, n0))) + n0 * np.eye(n0)
    B = rng.standard_normal((n0, k))
    got = ops.trsm_substitution(jnp.asarray(L, jnp.float32),
                                jnp.asarray(B, jnp.float32),
                                accum_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.trsm_ref(
                                   jnp.asarray(L, jnp.float32),
                                   jnp.asarray(B, jnp.float32))),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtypes,want", [
    ((jnp.float32,), jax.lax.Precision.HIGHEST),
    ((jnp.float32, jnp.float32), jax.lax.Precision.HIGHEST),
    ((jnp.bfloat16, jnp.float32), jax.lax.Precision.HIGHEST),
    ((jnp.bfloat16,), None),
    ((jnp.bfloat16, jnp.bfloat16), None),
])
def test_gemm_precision_is_highest_for_f32_only(dtypes, want):
    """f32 GEMM operands run at HIGHEST (a TPU's default is one bf16
    pass); bf16 operands keep the default precision."""
    from repro.core.precision import gemm_precision
    assert gemm_precision(*dtypes) == want
    arrays = [jnp.zeros((2, 2), d) for d in dtypes]
    assert gemm_precision(*arrays) == want
