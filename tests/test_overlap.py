"""Software-pipelined sweep (overlap) + measured-cost calibration
(DESIGN.md Sec. 16): spec normalization and cache-key discipline,
bit-identity of the overlapped sweep per precision preset, the
zero-retrace / zero-transfer steady state with overlap on, the async
comm primitives on degenerate meshes (and their sync compat fallback),
PipelinedCost algebra, and the fit/load calibration layer that the
planners price from.

Multi-device bit-identity (p1=2 grids, degenerate p2=1 / p1=1 axes,
structured sweeps) runs out-of-process in the slow tier:
``repro.core.selfcheck overlap`` via tests/test_core_distributed.py.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro import api, compat
from repro.core import comm, session, tuning
from repro.core import cost_model as cm
from repro.core.solver import SolveSpec, UpdateSpec, _normalize_overlap
from repro.core.structure import FactorStructure

pytestmark = pytest.mark.overlap

PRESET_CASES = [
    (None, np.float64, 1e-10),
    ("fp32", np.float32, 1e-5),
    ("bf16", np.float32, 5e-2),
    ("bf16_refine", np.float32, 1e-5),
    ("fp64_refine", np.float64, 1e-11),
]


@pytest.fixture(autouse=True, scope="module")
def _x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="module")
def grid():
    return api.make_trsm_mesh(1, 1)


def _factor(n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    L = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    return L.astype(dtype), rng


# --------------------- spec field normalization ---------------------

def test_overlap_spelling_normalization():
    assert _normalize_overlap("auto") == "on"
    assert _normalize_overlap(True) == "on"
    assert _normalize_overlap("on") == "on"
    assert _normalize_overlap("off") is None
    assert _normalize_overlap(False) is None
    assert _normalize_overlap(None) is None
    with pytest.raises(ValueError, match="overlap"):
        _normalize_overlap("maybe")


def test_spec_normalizes_overlap_like_structure():
    """``overlap="off"`` must be byte-for-byte the pre-overlap spec —
    the same normalize-to-None discipline as structure=dense — so
    committed cache keys and plan hashes are stable across the
    refactor."""
    from repro.core import precision
    kw = dict(n=64, k=8, grid=api.plan_grid(2, 1), n0=16,
              policy=precision.PRESETS["fp32"])
    on = SolveSpec(**kw)                       # default "auto" -> "on"
    assert on.overlap == "on"
    off = SolveSpec(**kw, overlap="off")
    assert off.overlap is None
    assert off == SolveSpec(**kw, overlap=False)
    assert off == SolveSpec(**kw, overlap=None)
    assert hash(off) == hash(SolveSpec(**kw, overlap=None))
    assert off == dataclasses.replace(on, overlap="off")
    assert on != off
    with pytest.raises(ValueError, match="overlap"):
        SolveSpec(**kw, overlap="sometimes")


def test_auto_spec_carries_overlap():
    spec = SolveSpec.auto(64, 8, p=4)
    assert spec.overlap == "on"
    assert SolveSpec.auto(64, 8, p=4, overlap="off").overlap is None


def test_update_spec_overlap_always_none(grid):
    """Admission has no steady-state sweep to pipeline: UpdateSpec
    validates the spelling but always normalizes to None, so admission
    program keys never fork on overlap."""
    bank = api.FactorBank(grid, 32, n0=8, dtype=np.float32)
    L, _ = _factor(32)
    bank.admit(L)
    assert bank.update_spec().overlap is None
    with pytest.raises(ValueError, match="overlap"):
        dataclasses.replace(bank.update_spec(), overlap="banana")


def test_solver_overlap_keys_distinct_programs(grid):
    L, _ = _factor(32)
    s_on = api.Solver.from_factor(L, grid, n0=8, overlap="on")
    s_off = api.Solver.from_factor(L, grid, n0=8, overlap="off")
    assert s_on.spec_for(4).overlap == "on"
    assert s_off.spec_for(4).overlap is None
    assert s_on.spec_for(4) != s_off.spec_for(4)
    # default is auto -> on
    assert api.Solver.from_factor(L, grid, n0=8).spec_for(4).overlap \
        == "on"


# ------------------------- bit-identity -------------------------

@pytest.mark.parametrize("precision,in_dt,rtol", PRESET_CASES)
def test_overlap_bit_identity_per_preset(grid, precision, in_dt, rtol):
    """The pipelined sweep issues the SAME collectives on the same
    operands in a different order: the solve must be byte-equal to the
    sequential sweep for every precision preset, not merely close."""
    n, k = 32, 4
    L, rng = _factor(n, dtype=in_dt)
    B = rng.standard_normal((n, k)).astype(in_dt)
    outs = {}
    for ov in ("on", "off"):
        solver = api.Solver.from_factor(
            L, grid, n0=8, precision=precision,
            dtype=None if precision else in_dt, overlap=ov)
        outs[ov] = np.asarray(solver.solve(B, donate=False))
    assert outs["on"].tobytes() == outs["off"].tobytes()
    rel = (np.linalg.norm(L.astype(np.float64) @ outs["on"] - B)
           / np.linalg.norm(B))
    assert rel < rtol


@pytest.mark.parametrize("method", ["inv", "rec"])
def test_overlap_bit_identity_methods(grid, method):
    n, k = 64, 8
    L, rng = _factor(n, dtype=np.float64)
    B = rng.standard_normal((n, k))
    outs = {}
    for ov in ("on", "off"):
        solver = api.Solver.from_factor(L, grid, method=method, n0=16,
                                        overlap=ov)
        outs[ov] = np.asarray(solver.solve(B, donate=False))
    assert outs["on"].tobytes() == outs["off"].tobytes()


def test_overlap_bit_identity_structured(grid):
    n, k = 64, 8
    st = FactorStructure.banded(16)
    rng = np.random.default_rng(3)
    L = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    L *= np.abs(np.arange(n)[:, None] - np.arange(n)[None, :]) < 16
    B = rng.standard_normal((n, k))
    outs = {}
    for ov in ("on", "off"):
        solver = api.Solver.from_factor(L, grid, n0=16, structure=st,
                                        overlap=ov)
        outs[ov] = np.asarray(solver.solve(B, donate=False))
    assert outs["on"].tobytes() == outs["off"].tobytes()


# ------------------ steady state with overlap on ------------------

def test_overlap_on_steady_state_zero_retrace_zero_transfer(grid):
    """The acceptance invariant (DESIGN.md Secs. 10/16) with the
    pipelined sweep: one trace at warmup, then repeated solves move no
    host data and retrace nothing."""
    n, k = 32, 4
    L, rng = _factor(n, dtype=np.float32)
    # a private program cache: the trace-count bump is then exactly
    # this solver's warmup, independent of specs other tests built
    solver = api.Solver.from_factor(L, grid, n0=8, overlap="on",
                                    cache=session.CompiledSolverCache())
    key = solver.program_for(k).key
    assert key.overlap == "on"
    before = session.TRACE_COUNTS[key]
    solver.warmup(k)
    assert session.TRACE_COUNTS[key] == before + 1
    Bs = [solver.place_rhs(rng.standard_normal((n, k)).astype(np.float32))
          for _ in range(3)]
    with jax.transfer_guard("disallow"):
        outs = [solver.solve(b) for b in Bs]
    assert session.TRACE_COUNTS[key] == before + 1
    for x in outs:
        assert np.isfinite(np.asarray(x)).all()


# ---------------- async comm primitives, degenerate mesh ----------------

def test_async_primitives_value_equal_sync_on_degenerate_mesh(grid):
    """p1 = p2 = 1: every axis is a singleton, the hardest degenerate
    case for a start/finish split (gathers are reshapes, permutes are
    identity).  The async pair must return exactly the sync wrapper's
    value."""
    from jax.sharding import PartitionSpec as P

    def sync_body(x):
        g = comm.all_gather(x, "z", axis=0, tiled=True)
        return comm.ppermute(g, "x", [(0, 0)])

    def async_body(x):
        h = comm.all_gather_start(x, "z", axis=0, tiled=True)
        g = comm.all_gather_finish(h)
        hp = comm.ppermute_start(g, "x", [(0, 0)])
        return comm.ppermute_finish(hp)

    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    outs = {}
    for name, body in [("sync", sync_body), ("async", async_body)]:
        # a gathered/permuted value is typed varying over its axes
        f = compat.shard_map(body, mesh=grid.mesh, in_specs=P(),
                             out_specs=P(("z", "x")))
        outs[name] = np.asarray(jax.jit(f)(x))
    assert np.array_equal(outs["sync"], outs["async"])
    assert np.array_equal(outs["sync"], x)      # singleton axes: no-op


def test_async_pair_prices_identically_to_sync(grid):
    """The cost is recorded once, at start — a start/finish pair must
    trace to the SAME (s, w, f) as the synchronous wrapper it
    replaces, so overlapped and sequential sweeps report identical
    counts."""
    from jax.sharding import PartitionSpec as P

    def sync_body(x):
        return comm.all_gather(x, "z", axis=0, tiled=False)

    def async_body(x):
        return comm.all_gather_finish(
            comm.all_gather_start(x, "z", axis=0, tiled=False))

    x = jax.ShapeDtypeStruct((4, 4), np.float32)
    costs = {}
    for name, body in [("sync", sync_body), ("async", async_body)]:
        f = compat.shard_map(body, mesh=grid.mesh, in_specs=P(),
                             out_specs=P("z"))
        costs[name] = comm.traced_cost(jax.jit(f), x)
    assert costs["sync"].s == costs["async"].s
    assert costs["sync"].w == costs["async"].w
    assert costs["sync"].f == costs["async"].f


def test_compat_fallback_contract():
    """The installed jax has no async collective API: the compat
    handles must be the gathered values themselves (eager issue +
    identity finish)."""
    # identity-finish: finishing twice is harmless
    from jax.sharding import PartitionSpec as P
    g = api.make_trsm_mesh(1, 1)

    def body(x):
        h = compat.async_all_gather_start(x, "y", axis=0, tiled=True)
        return compat.async_all_gather_finish(
            compat.async_all_gather_finish(h))

    x = np.ones((2, 2), np.float32)
    f = compat.shard_map(body, mesh=g.mesh, in_specs=P(),
                         out_specs=P("y"))
    assert np.array_equal(np.asarray(jax.jit(f)(x)), x)


# ------------------------ PipelinedCost algebra ------------------------

def test_pipelined_cost_counts_invariant_time_max():
    m = cm.tpu_v5e()
    comm_c = cm.Cost(s=4, w=1e6)
    comp_c = cm.Cost(f=5e9)
    p = cm.pipelined(comm_c, comp_c)
    # overlap hides time, not traffic
    assert (p.s, p.w, p.f) == (comm_c.s, comm_c.w, comp_c.f)
    assert p.time(m) == pytest.approx(
        max(comm_c.time(m), comp_c.time(m)))
    assert p.serial().time(m) == pytest.approx(
        comm_c.time(m) + comp_c.time(m))
    assert p.time(m) <= p.serial().time(m)
    # stages concatenate; plain Cost lifts to a serial stage
    q = p + p
    assert q.time(m) == pytest.approx(2 * p.time(m))
    extra = cm.Cost(s=1, w=10, f=10)
    assert (p + extra).time(m) == pytest.approx(
        p.time(m) + extra.time(m))
    assert (extra + p).time(m) == pytest.approx(
        p.time(m) + extra.time(m))
    assert (2 * p).w == pytest.approx(2 * p.w)


def test_steady_cost_overlap_never_slower_in_model():
    m = cm.tpu_v5e()
    for (n, k, n0, p1, p2) in [(4096, 64, 256, 2, 2), (65536, 256, 1024,
                                                       8, 4)]:
        seq = cm.it_inv_trsm_steady_cost(n, k, n0, p1, p2)
        ov = cm.it_inv_trsm_steady_cost(n, k, n0, p1, p2, overlap=True)
        assert isinstance(ov, cm.PipelinedCost)
        assert (ov.s, ov.w, ov.f) == (seq.s, seq.w, seq.f)
        assert ov.time(m) <= seq.time(m)


def test_structured_overlap_cost_scales_both_sides():
    st = FactorStructure.banded(512 // 8)
    dense = cm.it_inv_trsm_steady_cost(512, 16, 64, 2, 1, overlap=True)
    strct = cm.it_inv_trsm_steady_cost(512, 16, 64, 2, 1, structure=st,
                                       overlap=True)
    assert strct.w < dense.w and strct.f < dense.f
    assert strct.time(cm.tpu_v5e()) < dense.time(cm.tpu_v5e())


# -------------------------- calibration --------------------------

def test_fit_calibration_recovers_synthetic_scales():
    base = cm.tpu_v5e()
    truth = cm.Calibration(a=3.0, b=0.5, g=2.0)
    tm = truth.apply(base)
    rng = np.random.default_rng(0)
    rows = []
    for _ in range(12):
        s = float(rng.uniform(10, 1e4))
        w = float(rng.uniform(1e4, 1e8))
        f = float(rng.uniform(1e6, 1e12))
        c = cm.Cost(s=s, w=w, f=f)
        rows.append(dict(s=s, w=w, f=f, measured_s=c.time(tm),
                         predicted_s=c.time(base)))
    cal = cm.fit_calibration(rows, base, dispatch_s=1e-5)
    assert cal.a == pytest.approx(truth.a, rel=1e-6)
    assert cal.b == pytest.approx(truth.b, rel=1e-6)
    assert cal.g == pytest.approx(truth.g, rel=1e-6)
    assert cal.dispatch_s == 1e-5
    calm = cal.apply(base)
    assert calm.name == base.name + "+cal"
    err0 = np.median([abs(r["predicted_s"] - r["measured_s"])
                      / r["measured_s"] for r in rows])
    err1 = np.median([abs(cm.Cost(r["s"], r["w"], r["f"]).time(calm)
                          - r["measured_s"]) / r["measured_s"]
                      for r in rows])
    assert err1 * 2 <= err0


def test_load_calibration_roundtrip(tmp_path):
    # loads are cached per path, so probe missing/corrupt on paths of
    # their own
    assert cm.load_calibration(tmp_path / "absent.json") is None
    p = tmp_path / "BENCH_overlap.json"
    p.write_text(json.dumps(dict(calibration=dict(
        a=1.5, b=0.8, g=1.1, dispatch_s=2e-5))))
    cal = cm.load_calibration(p)
    assert cal == cm.Calibration(a=1.5, b=0.8, g=1.1, dispatch_s=2e-5)
    junk = tmp_path / "junk.json"
    junk.write_text("{not json")
    assert cm.load_calibration(junk) is None       # corrupt -> None


def test_committed_calibration_drives_planners():
    """The committed BENCH_overlap.json must load, and every a-priori
    entry point (default_machine, default_dispatch_s, plan_fleet's
    defaults) must price from it."""
    cal = cm.load_calibration()
    assert cal is not None, (
        "benchmarks/BENCH_overlap.json missing or has no calibration "
        "block: regenerate with `python -m benchmarks.run paper_table`")
    assert cal.a > 0 and cal.b > 0 and cal.g > 0
    assert cal.dispatch_s and cal.dispatch_s > 0
    assert tuning.calibration() == cal
    m = tuning.default_machine()
    base = cm.tpu_v5e()
    assert m.name == base.name + "+cal"
    assert m.alpha == pytest.approx(base.alpha * cal.a)
    assert m.beta == pytest.approx(base.beta * cal.b)
    assert m.gamma == pytest.approx(base.gamma * cal.g)
    assert tuning.default_dispatch_s(123.0) == cal.dispatch_s


def test_calibration_plan_shift_is_the_expected_one():
    """The fitted rescale deliberately moves the latency/bandwidth/
    compute balance; any plan change it induces is pinned HERE, so a
    recalibration that silently flips plans fails loudly instead.
    The committed fit (alpha up ~3 orders on simulated-host timings)
    pushes latency-sensitive regimes toward fewer, larger blocks and
    the rec/inv dispatch toward rec on latency-bound shapes."""
    base = cm.tpu_v5e()
    calm = tuning.default_machine()
    regimes = [(16384, 128, 64), (16384, 512, 256), (4096, 64, 16),
               (256, 65536, 64), (1024, 32, 8)]
    shifts = []
    for (n, k, p) in regimes:
        s_base = tuning.tune(n, k, p, machine=base)
        s_cal = tuning.tune(n, k, p)     # calibrated default
        # every calibrated plan is still feasible
        spec = SolveSpec.auto(n, k, p=p)
        spec.validate()
        if (s_base.n0, s_base.p1, s_base.p2) != \
                (s_cal.n0, s_cal.p1, s_cal.p2):
            shifts.append((n, k, p))
    # the shift set is pinned: update deliberately on recalibration
    assert shifts == PINNED_PLAN_SHIFTS, (
        f"calibration changed auto plans for {shifts}; if intended, "
        f"update PINNED_PLAN_SHIFTS and the DESIGN.md Sec. 16 note")


# concrete (n, k, p) regimes whose SolveSpec.auto plan differs under
# the committed calibration vs nominal constants (empty = the current
# fit shifts rates without crossing any argmin boundary)
PINNED_PLAN_SHIFTS = [(16384, 128, 64), (16384, 512, 256),
                      (4096, 64, 16), (1024, 32, 8)]
