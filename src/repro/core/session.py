"""Device-resident solve pipeline: compiled-solver cache + TrsmSession.

The paper's algorithms avoid *inter-processor* communication; this
module removes the remaining *host* communication from the end-to-end
entry points.  Historically every ``core.trsm`` call copied L/B to host
NumPy, permuted to cyclic storage on the CPU, re-uploaded, and re-traced
the shard_map program — a round-trip that dwarfs the collectives the
algorithm saves.  ScaLAPACK-style practice keeps factors resident in
distributed block-cyclic storage; this module does the same:

* ``CompiledSolverCache`` — an LRU of compiled solve programs keyed by
  :class:`repro.core.solver.SolveSpec` (the frozen declarative solve
  description; the SOLE key type — see DESIGN.md Sec. 10).  Each
  program fuses, in ONE jitted computation: the
  on-device cyclic permutation of B (with the upper/transpose reversal
  identity folded into the gather), the shard_map solver, the inverse
  permutation of X back to natural layout, and — when the precision
  policy refines — the fixed-trip iterative-refinement loop
  (``repro.core.refine``).  B's buffer is donated in the serving
  variant.
* ``TrsmSession`` — DEPRECATED shim over
  :class:`repro.core.solver.Solver` (``Solver.from_factor``): one
  resident factor served with zero steady-state host<->device
  transfers and zero retraces FOR EVERY PRECISION POLICY (asserted in
  tests via :data:`TRACE_COUNTS` and ``jax.transfer_guard``).  New
  code uses ``repro.api``.

Precision (DESIGN.md Sec. 7): a :class:`repro.core.precision
.PrecisionPolicy` splits the pipeline's dtypes into storage / compute /
accumulate / residual roles.  The factor is cast ONCE at distribution
time — to the storage dtype for the sweep and, when the policy refines,
additionally to the residual dtype for the on-device residual GEMM —
and the refinement loop is unrolled into the same compiled program, so
a ``bf16_refine`` session serves fp32-accurate solves with bf16 (MXU
native) sweep GEMMs and no extra host traffic.

Operator reductions (DESIGN.md Sec. 3), folded into distribution-time
gathers so the sweep only ever sees a lower-triangular operand:
    lower, op(L)=L      : Leff = L
    upper, op(U)=U      : Leff = JUJ   (reverse rows+cols), B/X reversed
    lower, op(L)=L^T    : Leff = J L^T J (transpose+reverse), B/X reversed
    upper, op(U)=U^T    : Leff = U^T  (transpose only)
i.e. transpose <=> ``transpose`` flag, reversal <=> ``lower ==
transpose``.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import grid as gridlib
from repro.core import precision as preclib
from repro.core import refine as refinelib
from repro.core.grid import TrsmGrid
from repro.core.precision import PrecisionPolicy

# Retrace telemetry: bumped at *trace time* of each cached program, so a
# test can assert steady-state solves never re-trace (key -> count).
# Refined programs bump ONCE per trace, not once per inner sweep.
TRACE_COUNTS: collections.Counter = collections.Counter()


def _needs_reversal(lower: bool, transpose: bool) -> bool:
    return lower == transpose


@dataclasses.dataclass(frozen=True)
class SolverProgram:
    """A compiled (prep, solve) pair for one solve configuration.

    ``prep(L_nat) -> factor`` distributes the factor once: an on-device
    gather to cyclic storage with the operator reduction folded in,
    cast to the policy's storage dtype — plus a second, residual-dtype
    copy when the policy refines.  The result is an opaque tuple;
    treat it as the token that ``solve`` consumes.

    ``solve(factor, B_nat) -> X_nat`` is the steady-state program:
    B-permute -> sweep -> X-unpermute, with the policy's refinement
    passes unrolled inside.  ``solve_donating`` additionally donates
    B's buffer (serving path — the caller must not reuse B afterwards).

    ``rhs_sharding`` is the pinned natural-layout placement of B (and
    of the returned X): requests placed there up front (``jax.device_put``
    — see ``TrsmSession.place_rhs``) enter the program with no input
    resharding at all, so the steady state is literally transfer-free.

    Remaining fields record the resolved plan: ``method`` ("inv"/"rec"),
    ``mode`` (the inv phase-1 scheme), ``n0`` (diagonal-block size) and
    ``policy`` (the :class:`PrecisionPolicy` the program was built for).

    ``collectives()`` is the :class:`repro.core.comm.CostTrace` of one
    call of ``solve``: its body traced once on abstract operands
    (nothing compiles or runs), memoized.  ``residual_tile_share`` is
    the share of the dense refinement residual's multiply-adds that
    ``solve`` does (``refine.residual_tile_share``: below 1 where the
    residual is row-blocked, on one device), None when the policy does
    not refine.
    """
    key: object                  # the program's SolveSpec (cache key)
    prep: Callable
    solve: Callable
    solve_donating: Callable
    rhs_sharding: object
    method: str
    mode: str | None
    n0: int | None
    policy: PrecisionPolicy
    collectives: Callable
    residual_tile_share: float | None


class CompiledSolverCache:
    """LRU cache of :class:`SolverProgram`s, keyed by
    :class:`repro.core.solver.SolveSpec` — the sole key type.

    A spec carries everything that changes the compiled artifact (the
    solve shape, plan, operator variant, precision policy, grid/mesh
    identity, bank width and map mode — the field-by-field table is
    DESIGN.md Sec. 10), so two call sites that build equal specs share
    one compiled program and nothing can be left out of the key by
    accident.  The positional-tuple keys of PRs 1-3 are gone;
    ``get`` rejects non-spec keys.

    Thread-safe; eviction drops the jitted callables (XLA frees the
    executables with them).  Builds are single-flight per key: when two
    threads miss the same spec concurrently, exactly one runs
    ``build()`` (a trace/compile can take minutes) and the other waits
    for the finished program — one miss per build, a hit for every
    waiter, so the counters stay meaningful under contention.
    """

    def __init__(self, maxsize: int = 32):
        self.maxsize = maxsize
        self._lock = threading.Lock()
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._inflight: dict = {}          # key -> Event of the builder
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key, build: Callable):
        from repro.core.solver import SolveSpec, UpdateSpec
        if not isinstance(key, (SolveSpec, UpdateSpec)):
            raise TypeError(
                f"CompiledSolverCache keys are SolveSpec (or UpdateSpec)"
                f" instances, got {type(key).__name__} (positional-tuple"
                f" keys were removed; build a spec via "
                f"repro.api.SolveSpec)")
        while True:
            with self._lock:
                if key in self._entries:
                    self._entries.move_to_end(key)
                    self.hits += 1
                    return self._entries[key]
                event = self._inflight.get(key)
                if event is None:          # we are the builder
                    event = threading.Event()
                    self._inflight[key] = event
                    self.misses += 1
                    break
            # another thread is building this key: wait for it, then
            # re-check (the entry is there on success; on a failed
            # build the waiter loops around and becomes the builder)
            event.wait()
        try:
            value = build()      # build outside the lock (tracing is slow)
        except BaseException:
            with self._lock:
                self._inflight.pop(key, None)
            event.set()
            raise
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
            self._inflight.pop(key, None)
        event.set()
        return value

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries

    def stats(self) -> dict:
        """Observability snapshot: size/hits/misses/evictions plus the
        derived hit rate (surfaced by ``launch.serve --cache-stats``)."""
        with self._lock:
            total = self.hits + self.misses
            return dict(size=len(self._entries), hits=self.hits,
                        misses=self.misses, evictions=self.evictions,
                        hit_rate=self.hits / total if total else 0.0)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = self.misses = self.evictions = 0


_DEFAULT_CACHE = CompiledSolverCache()


def default_cache() -> CompiledSolverCache:
    """The process-wide program cache used by ``core.trsm`` and every
    session that does not pass an explicit ``cache=``."""
    return _DEFAULT_CACHE


# ------------------------- program construction -------------------------

@functools.lru_cache(maxsize=128)
def _build_prep(grid: TrsmGrid, lower: bool, transpose: bool, dtype,
                stacked: bool = False, structure=None,
                n0: int | None = None):
    """Jitted L_nat -> L_cyc distribution (shared by both methods: rec
    and inv use the same P("x", ("z","y")) factor layout).  Memoized on
    its full key — including the target dtype, so a refining policy's
    storage- and residual-precision copies are two entries — and every
    RHS width and every session for the same configuration reuses one
    traced program.  ``stacked`` builds the factor-bank variant: the
    SAME fused gather applied to an (M, n, n) stack in one program
    (grid.cyclic_matrix_device permutes the trailing two axes), output
    sharded P(None, "x", ("z","y")).

    A non-dense ``structure`` (with its serving block size ``n0`` —
    both join the memo key) ENFORCES the declared block structure at
    admission: every element outside the block mask is zeroed (in
    natural layout, before the gather), which is what makes the
    level-scheduled sweep's skipped blocks mathematically safe
    (DESIGN.md Sec. 14)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.structure import apply_block_mask
    p1, p2 = grid.p1, grid.p2
    rev = _needs_reversal(lower, transpose)

    def prep(L):
        L = jnp.asarray(L, dtype)
        if structure is not None and not structure.is_dense:
            L = apply_block_mask(L, structure, n0)
        return gridlib.cyclic_matrix_device(
            L, p1, p1 * p2, reverse_rows=rev, reverse_cols=rev,
            transpose=transpose)

    spec = P(None, *grid.spec_L()) if stacked else grid.spec_L()
    return jax.jit(prep, out_shardings=NamedSharding(grid.mesh, spec))


def _factor_preps(grid: TrsmGrid, lower: bool, transpose: bool,
                  policy: PrecisionPolicy, stacked: bool = False,
                  structure=None, n0: int | None = None) -> tuple:
    """The (storage[, residual]) distribution programs for a policy.
    Both copies mask to ``structure``: the refinement residual must see
    the same (masked) operator the sweep solves against."""
    preps = (_build_prep(grid, lower, transpose, policy.storage_dtype,
                         stacked, structure, n0),)
    if policy.refines:
        preps += (_build_prep(grid, lower, transpose,
                              policy.residual_dtype, stacked,
                              structure, n0),)
    return preps


@functools.lru_cache(maxsize=128)
def _build_phase1(grid: TrsmGrid, n: int, n0: int, mode: str,
                  accum, block_inv, stacked: bool = False):
    """Jitted phase-1 program L_cyc -> Dt (the inverted diagonal
    faces), shared by factor-bank admission and banked-program prep.
    ``stacked`` maps it over a leading factor axis (one program inverts
    a whole (M, n, n) stack's diagonal blocks)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import inv_trsm
    prog = inv_trsm.it_inv_phase1_sharded(
        grid, n, n0, mode=mode,
        accum_dtype=jnp.dtype(accum) if accum is not None else None,
        block_inv=block_inv)
    fn = jax.vmap(prog) if stacked else prog
    spec = P(None, *inv_trsm.SPEC_DT) if stacked else inv_trsm.SPEC_DT
    return jax.jit(fn, out_shardings=NamedSharding(grid.mesh, spec))


def _check_policy_supported(policy: PrecisionPolicy) -> None:
    for role in (policy.storage_dtype, policy.compute_dtype,
                 policy.accumulate_dtype, policy.residual_dtype):
        if role == jnp.dtype("float64") and \
                jax.dtypes.canonicalize_dtype(jnp.float64) != jnp.float64:
            raise ValueError(
                f"precision policy {policy.name!r} needs float64; enable "
                f"jax_enable_x64 (jax.config.update('jax_enable_x64', "
                f"True)) before building the solver")


def _build_solver(spec) -> SolverProgram:
    """Build the compiled (prep, solve) program pair for a concrete
    :class:`repro.core.solver.SolveSpec` (which is also the program's
    cache key and TRACE_COUNTS key)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    grid, key = spec.grid, spec
    n, k, n0 = spec.n, spec.k, spec.n0
    policy, method, mode = spec.policy, spec.method, spec.mode
    lower, transpose = spec.lower, spec.transpose
    block_inv = spec.block_inv
    bank, map_mode = spec.bank_width, spec.map_mode or "vmap"
    p1, p2 = grid.p1, grid.p2
    rev = _needs_reversal(lower, transpose)
    compute = policy.compute_dtype
    accum = policy.accumulate_dtype

    # Batched-bank programs map ONLY the cyclic-storage sweep over the
    # leading factor axis ("vmap": every sweep step is an M-wide
    # batched GEMM; "scan": factors serialized inside the same single
    # program, memory-lean for large banks).  Everything around the
    # sweep stays stack-level: the B-permute / X-unpermute are per-axis
    # row permutations IDENTICAL across factors, so they run as ONE
    # batched gather for the whole (M, n, k) stack, and the refinement
    # residual is one batched GEMM between two such gathers
    # (apply_cyclic_operator on stacked operands).
    def _map_factors(fn):
        if map_mode == "vmap":
            return jax.vmap(fn)

        def scanned(*stacks):
            return jax.lax.scan(lambda c, xs: (c, fn(*xs)), None,
                                stacks)[1]
        return scanned

    prefactored = bank is not None and method == "inv"
    if method == "inv":
        from repro.core import inv_trsm
        resolved_mode = mode or inv_trsm.pick_phase1_mode(n, n0, grid)
        # natural-B placement: columns over z (matching spec_B), rows
        # replicated so the row-permutation gather is shard-local.
        rhs_spec = P(None, "z")

        if prefactored:
            # Banked steady state: the diagonal-block inversion was
            # hoisted to admission (the factor is immutable), so the
            # program is the sweep alone against the resident Dt —
            # unrolled, so mapping over factors yields straight-line
            # batched GEMMs (DESIGN.md Sec. 9).  Unrolling is capped:
            # a factor order with no good power-of-two divisor can pin
            # n0 = 1, and a straight-line m = n sweep would blow up
            # trace/compile time — past the cap the sweep keeps its
            # fori_loop (still one mapped program).  A non-dense
            # structure compiles the LEVEL-SCHEDULED sweep (static
            # skip/slice decisions per block column, DESIGN.md
            # Sec. 14), which needs the unroll and overrides the cap.
            sweep = _map_factors(inv_trsm.it_inv_sweep_sharded(
                grid, n, k, n0, accum_dtype=accum,
                unroll=(n // n0) <= 64, structure=spec.structure,
                overlap=spec.overlap == "on"))

            def base_solve(L_pair, B):
                B_cyc = gridlib.cyclic_rows_device(
                    jnp.asarray(B, compute), p1, reverse=rev)
                X_cyc = sweep(L_pair[0], L_pair[1], B_cyc)
                return gridlib.cyclic_rows_device(X_cyc, p1, inverse=True,
                                                  reverse=rev)
        else:
            sharded = inv_trsm.it_inv_trsm_sharded(grid, n, k, n0,
                                                   block_inv=block_inv,
                                                   mode=resolved_mode,
                                                   accum_dtype=accum,
                                                   overlap=spec.overlap
                                                   == "on")

            def base_solve(L_cyc, B):
                B_cyc = gridlib.cyclic_rows_device(
                    jnp.asarray(B, compute), p1, reverse=rev)
                X_cyc = sharded(L_cyc, B_cyc)
                return gridlib.cyclic_rows_device(X_cyc, p1, inverse=True,
                                                  reverse=rev)
    elif method == "rec":
        from repro.core import rec_trsm
        resolved_mode = None
        sharded = rec_trsm.rec_trsm_sharded(grid, n, k, n0,
                                            accum_dtype=accum,
                                            overlap=spec.overlap == "on")
        if bank is not None:
            sharded = _map_factors(sharded)
        rhs_spec = P(None, ("z", "y"))

        def base_solve(L_cyc, B):
            B_cyc = gridlib.cyclic_matrix_device(
                jnp.asarray(B, compute), p1, p1 * p2, reverse_rows=rev)
            X_cyc = sharded(L_cyc, B_cyc)
            return gridlib.cyclic_matrix_device(
                X_cyc, p1, p1 * p2, inverse=True, reverse_rows=rev)
    else:
        raise ValueError(f"unknown method {method!r}")

    # Factor tuple layout (flat, shardable): (L_lo[, Dt][, L_hi]) — Dt
    # present only for prefactored (banked inv) programs, where the
    # sweep operand is the (L_lo, Dt) pair.  The refinement loop is
    # dimension-agnostic, so the SAME body serves single factors and
    # whole banks.
    def split(factor):
        L_sweep = (factor[0], factor[1]) if prefactored else factor[0]
        L_hi = factor[-1] if policy.refines else None
        return L_sweep, L_hi

    def body(factor, B):
        L_sweep, L_hi = split(factor)
        return refinelib.refined_solve(base_solve, L_sweep, L_hi, B,
                                       policy=policy, p1=p1, p2=p2,
                                       reverse=rev)

    def program(factor, B):
        TRACE_COUNTS[key] += 1
        return body(factor, B)

    stacked = bank is not None
    preps = _factor_preps(grid, lower, transpose, policy, stacked,
                          spec.structure, n0)
    if prefactored:
        ph1 = _build_phase1(grid, n, n0, resolved_mode, accum, block_inv,
                            stacked)

        def prep_fn(L):
            parts = tuple(p(L) for p in preps)     # (L_lo[, L_hi])
            return (parts[0], ph1(parts[0])) + parts[1:]
    else:
        def prep_fn(L):
            return tuple(p(L) for p in preps)

    lead = (bank,) if stacked else ()

    def _lead(spec):
        return P(None, *spec) if stacked else spec

    # (shape, dtype, spec) of each factor role: L_lo[, Dt][, L_hi]
    roles = [((n, n), policy.storage_dtype, grid.spec_L())]
    if prefactored:
        from repro.core.inv_trsm import SPEC_DT, dt_shape
        roles.append((dt_shape(n, n0), policy.storage_dtype, SPEC_DT))
    if policy.refines:
        roles.append(((n, n), policy.residual_dtype, grid.spec_L()))
    factor_sh = tuple(NamedSharding(grid.mesh, _lead(spec))
                      for _, _, spec in roles)
    rhs_sh = NamedSharding(grid.mesh, _lead(rhs_spec))

    @functools.cache
    def collectives():
        from repro.core import comm
        factor = tuple(jax.ShapeDtypeStruct(lead + shape, dt, sharding=sh)
                       for (shape, dt, _), sh in zip(roles, factor_sh))
        B = jax.ShapeDtypeStruct(lead + (n, k), policy.io_dtype,
                                 sharding=rhs_sh)
        return comm.traced_cost(body, factor, B)

    jit_kw = dict(in_shardings=(factor_sh, rhs_sh),
                  out_shardings=rhs_sh)
    return SolverProgram(
        key=key,
        prep=prep_fn,
        solve=jax.jit(program, **jit_kw),
        solve_donating=jax.jit(program, donate_argnums=(1,), **jit_kw),
        rhs_sharding=rhs_sh,
        method=method, mode=resolved_mode, n0=n0, policy=policy,
        collectives=collectives,
        residual_tile_share=refinelib.residual_tile_share(n, p1, p2)
        if policy.refines else None)


@dataclasses.dataclass(frozen=True)
class UpdaterProgram:
    """A compiled in-place bank updater for one
    :class:`repro.core.solver.UpdateSpec` (DESIGN.md Sec. 11).

    ``update(stacks, slot, L) -> stacks`` is ONE jitted program that
    re-runs the admission pipeline for a single factor — the fused
    distribution gather (operator reductions + policy dtype casts
    folded in; skipped for cyclic ingestion) and, for method "inv",
    the hoisted phase-1 diagonal-block inversion — and scatters every
    factor role (L_lo[, Dt][, L_hi]) into the resident (C, ...) stacks
    at ``slot`` via ``lax.dynamic_update_index_in_dim``.  The stacks
    argument is DONATED: XLA updates the resident buffers in place, so
    a replace moves one factor's worth of data, never the bank's.

    ``slot`` must be a device-resident int32 scalar (the bank pins one
    per slot at capacity allocation) so the steady-state churn path
    performs zero host->device transfers.
    """
    key: object                  # the program's UpdateSpec (cache key)
    update: Callable


def _build_updater(uspec) -> UpdaterProgram:
    """Build the compiled in-place updater for an
    :class:`repro.core.solver.UpdateSpec` (which is also its cache key
    and TRACE_COUNTS key)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    grid, key = uspec.grid, uspec
    policy = uspec.policy
    prefactored = uspec.method == "inv"
    chunked = uspec.chunk > 1
    if uspec.ingest == "natural":
        preps = _factor_preps(grid, uspec.lower, uspec.transpose, policy,
                              stacked=chunked,
                              structure=uspec.structure, n0=uspec.n0)
    if prefactored:
        ph1 = _build_phase1(grid, uspec.n, uspec.n0, uspec.mode,
                            policy.accumulate_dtype, uspec.block_inv,
                            stacked=chunked)

    def _pad(L):
        # blockdiag(L, I) at the bucket order: the padded tail rows are
        # e_i rows, so they solve to the (zero) padded RHS rows exactly,
        # and the zero coupling blocks keep the leading d x k solution
        # bit-identical to the unpadded order-d sweep (same n0).
        d, n = uspec.pad_from, uspec.n
        tail = jnp.arange(d, n)
        full = jnp.zeros((n, n), L.dtype).at[:d, :d].set(L)
        return full.at[tail, tail].set(jnp.ones((), L.dtype))

    def roles(L):
        if uspec.pad_from is not None:
            L = jax.vmap(_pad)(L) if chunked else _pad(L)
        if uspec.ingest == "natural":
            parts = tuple(p(L) for p in preps)         # (L_lo[, L_hi])
        else:                                          # cyclic: cast only
            dts = (policy.storage_dtype,)
            if policy.refines:
                dts += (policy.residual_dtype,)
            parts = tuple(jnp.asarray(L, dt) for dt in dts)
        if prefactored:
            parts = (parts[0], ph1(parts[0])) + parts[1:]
        return parts

    def update(stacks, slot, L):
        TRACE_COUNTS[key] += 1
        if chunked:                       # contiguous run of slots
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(s, r, slot, axis=0)
                for s, r in zip(stacks, roles(L)))
        return tuple(jax.lax.dynamic_update_index_in_dim(s, r, slot, 0)
                     for s, r in zip(stacks, roles(L)))

    specs = [grid.spec_L()]
    if prefactored:
        from repro.core.inv_trsm import SPEC_DT
        specs.append(SPEC_DT)
    if policy.refines:
        specs.append(grid.spec_L())
    stack_sh = tuple(NamedSharding(grid.mesh, P(None, *s)) for s in specs)
    return UpdaterProgram(
        key=key,
        update=jax.jit(update, donate_argnums=(0,),
                       out_shardings=stack_sh))


def resolve_plan(grid: TrsmGrid, n: int, k: int, *, method: str = "inv",
                 n0: int | None = None, machine=None):
    """Host-side (pure arithmetic) resolution of method/n0 so the cache
    key is concrete.  Delegates to the ONE resolution path,
    :func:`repro.core.solver.resolve_plan` (the former
    ``resolve_plan`` / ``tuning.tune`` / ``choose_method`` overlap,
    folded)."""
    from repro.core import solver as solverlib
    return solverlib.resolve_plan(grid, n, k, method=method, n0=n0,
                                  machine=machine)


def get_solver(grid: TrsmGrid, *, n: int, k: int, dtype=None,
               method: str = "inv", n0: int | None = None,
               mode: str | None = None, lower: bool = True,
               transpose: bool = False, machine=None,
               block_inv: Callable | None = None,
               precision=None,
               bank: int | None = None, map_mode: str = "vmap",
               cache: CompiledSolverCache | None = None) -> SolverProgram:
    """Fetch (or build) the compiled solve program for a configuration.

    ``precision`` accepts a preset name (``"fp32"``, ``"bf16"``,
    ``"bf16_refine"``, ``"fp64_refine"``) or a
    :class:`~repro.core.precision.PrecisionPolicy`; when omitted, the
    uniform single-dtype policy at ``dtype`` is used (the legacy
    pipeline).  Exactly one of ``precision`` / ``dtype`` is required.

    ``bank`` requests the BATCHED program over a stack of M factors
    (``repro.core.bank.FactorBank``): ``factor`` becomes a tuple of
    (M, n, n) stacks and B an (M, n, k) stack, solved in one dispatch
    by mapping the per-factor body over the leading axis with
    ``map_mode`` ("vmap" | "scan", see DESIGN.md Sec. 9).  The bank
    width (and map mode) join the cache key: banks of different widths
    are different compiled artifacts, while every same-width bank of
    the same configuration shares one program.
    """
    from repro.core import solver as solverlib
    if bank is not None and bank < 1:
        raise ValueError(f"bank width must be >= 1, got {bank}")
    if map_mode not in ("vmap", "scan"):
        raise ValueError(f"unknown map_mode {map_mode!r}")
    method, n0 = resolve_plan(grid, n, k, method=method, n0=n0,
                              machine=machine)
    spec = solverlib.SolveSpec(
        n=n, k=k, grid=grid, policy=preclib.resolve(precision, dtype),
        method=method, n0=n0, mode=mode, lower=lower,
        transpose=transpose, block_inv=block_inv, bank_width=bank,
        map_mode=map_mode if bank is not None else None)
    return solverlib.solver_for(spec, cache)


# ------------------------------ sessions ------------------------------

class TrsmSession:
    """DEPRECATED single-factor serving session — a thin shim over
    :meth:`repro.core.solver.Solver.from_factor` (a width-1 factor
    bank), kept for source compatibility; results are bit-identical to
    the :class:`~repro.core.solver.Solver` path.

    The contract is unchanged (the "cyclic-storage contract", see
    ROADMAP.md and DESIGN.md Secs. 4-5, 10): the factor is distributed
    ONCE at construction, never touches the host again, and ``solve``
    runs one compiled program per RHS shape with zero steady-state
    host<->device transfers and zero retraces for every precision
    policy.  New code:

        solver = repro.api.Solver.from_factor(L, grid, n0=16)
        X = solver.solve(B)
    """

    def __init__(self, L, grid: TrsmGrid, *, method: str = "inv",
                 n0: int | None = None, mode: str | None = None,
                 lower: bool = True, transpose: bool = False,
                 machine=None, block_inv: Callable | None = None,
                 dtype=None, precision=None,
                 cache: CompiledSolverCache | None = None):
        from repro.core import solver as solverlib
        solverlib._warn_deprecated("TrsmSession", "Solver.from_factor")
        with solverlib._shim_quiet():
            self._solver = solverlib.Solver.from_factor(
                L, grid, method=method, n0=n0, mode=mode, lower=lower,
                transpose=transpose, machine=machine,
                block_inv=block_inv, dtype=dtype, precision=precision,
                cache=cache)

    @classmethod
    def _wrap(cls, solver) -> "TrsmSession":
        self = object.__new__(cls)
        self._solver = solver
        return self

    # ------------- former attributes, read off the Solver -------------

    @property
    def n(self) -> int:
        return self._solver.n

    @property
    def grid(self) -> TrsmGrid:
        return self._solver.grid

    @property
    def policy(self) -> PrecisionPolicy:
        return self._solver.policy

    @property
    def dtype(self):
        return self._solver.dtype

    @property
    def method(self) -> str:
        return self._solver.method

    @property
    def n0(self) -> int | None:
        return self._solver.n0

    @property
    def mode(self) -> str | None:
        return self._solver.bank.mode

    @property
    def cache(self) -> CompiledSolverCache:
        return self._solver.cache

    @property
    def solves_served(self) -> int:
        return self._solver.solves_served

    @property
    def factor_cyclic(self):
        """The resident sweep factor (cyclic storage, storage dtype)."""
        return self._solver.bank.factors_cyclic[0]

    @property
    def factor_cyclic_residual(self):
        """The residual-precision resident copy (None unless the
        policy refines)."""
        res = self._solver.bank.factors_cyclic_residual
        return None if res is None else res[0]

    def program_for(self, k: int) -> SolverProgram:
        return self._solver.program_for(k)

    def place_rhs(self, B):
        """Pin an (n, k) right-hand side to the solve program's input
        placement, returned at the legacy (n, k) shape (``solve``
        lifts it to the width-1 stack internally with a pure on-device
        expand, so the steady state stays transfer-free)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        prog = self.program_for(B.shape[1])
        # the program's RHS sharding minus the leading factor axis
        sharding = NamedSharding(self.grid.mesh,
                                 P(*prog.rhs_sharding.spec[1:]))
        return jax.device_put(jnp.asarray(B, self.dtype), sharding)

    def solve(self, B, *, donate: bool = True):
        """Solve op(L) X = B; accepts an (n, k) RHS or the (1, n, k)
        placed form, returns X as (n, k)."""
        if B.ndim == 3 and B.shape[0] == 1:
            return jax.lax.squeeze(self._solver.solve(B, donate=donate),
                                   (0,))
        if B.ndim != 2 or B.shape[0] != self.n:
            raise ValueError(f"rhs must be ({self.n}, k), got {B.shape}")
        return self._solver.solve(B, donate=donate)

    def warmup(self, k: int) -> "TrsmSession":
        self._solver.warmup(k)
        return self
