"""Unified front door for the solve stack: SolveSpec + Solver +
SolveServer (DESIGN.md Sec. 10; re-exported as ``repro.api``).

The paper's central claim is that the *choice* of algorithm — the
block-inversion size n0 interpolating between standard TRSM and full
triangular inversion, the processor grid, and the method itself — can
be made **a priori** from the communication cost analysis (Sec. VIII).
After three PRs that decision was scattered over four entry points
(``tuning.tune``, ``tuning.choose_method``, ``session.resolve_plan``,
``session.get_solver``) and two parallel class hierarchies
(``TrsmSession``/``TrsmRequestServer`` vs ``BatchedTrsmSession``/
``BankedTrsmServer``), keyed by a brittle positional tuple.  This
module collapses all of it into three declarative pieces:

* :class:`SolveSpec` — a frozen, hashable description of ONE solve
  configuration: the problem (n, k, operator variant), the plan
  (method, n0, mode, grid — resolvable a priori via
  :meth:`SolveSpec.auto`, which consumes a frozen
  :class:`~repro.core.tuning.TrsmPlan` verbatim), and the execution
  policy (precision, bank width, map mode).  A concrete spec **is**
  the :class:`~repro.core.session.CompiledSolverCache` key — the sole
  key type; the positional tuples are gone.

* :class:`Solver` — ONE serving class subsuming the former
  ``TrsmSession`` (single resident factor) and ``BatchedTrsmSession``
  (bank of M factors): a :class:`~repro.core.bank.FactorBank` is the
  admission layer and a width-1 bank IS the single-factor case.
  Admission distributes each factor once (operator reductions folded
  into the gather, policy dtype casts, phase 1 — the paper's
  Diagonal-Inverter — hoisted for method "inv"); the steady state is
  one compiled program per RHS width with zero host<->device
  transfers and zero retraces, at any bank width, for every precision
  policy.

* :class:`SolveServer` — ONE continuous-batching front-end subsuming
  ``TrsmRequestServer``/``BankedTrsmServer``: per-factor request
  queues, first-fit packed fixed-width panels, one dispatch per wave
  covering every factor, submit-order results.

The deprecated names remain as thin shims (one ``DeprecationWarning``
each, bit-identical results) so existing call sites keep working;
internal code must use this module (CI errors on internal callers of
the deprecated API).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import threading
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core import errors as _errors
from repro.core import precision as preclib
from repro.core import spans
from repro.core.bank import FactorBank, as_factor
from repro.core.grid import TrsmGrid
from repro.core.precision import PrecisionPolicy
from repro.core.structure import FactorStructure


# --------------------------- deprecation shims ---------------------------

_QUIET = threading.local()


def _warn_deprecated(old: str, new: str) -> None:
    """One DeprecationWarning per deprecated entry point, attributed to
    the caller (stacklevel: helper -> shim -> caller).  Suppressed when
    a shim builds other shims internally (:func:`_shim_quiet`), so each
    deprecated call emits exactly ONE warning."""
    if getattr(_QUIET, "on", False):
        return
    warnings.warn(f"{old} is deprecated; use {new} (see the README "
                  f"migration table)", DeprecationWarning, stacklevel=3)


@contextlib.contextmanager
def _shim_quiet():
    prev = getattr(_QUIET, "on", False)
    _QUIET.on = True
    try:
        yield
    finally:
        _QUIET.on = prev


# ----------------------------- plan resolution -----------------------------

def plan_grid(p1: int, p2: int) -> TrsmGrid:
    """A mesh-less grid (p1 x p1 x p2) for plan-only specs: carries the
    processor-grid arithmetic of a :class:`SolveSpec` without touching
    devices.  Executable paths (:func:`solver_for`, :class:`Solver`)
    require a real mesh (``repro.core.grid.make_trsm_mesh``)."""
    return TrsmGrid(None, p1, p2)


def resolve_plan(grid: TrsmGrid, n: int, k: int, *, method: str = "inv",
                 n0: int | None = None, machine=None,
                 hoisted: bool = False,
                 structure: FactorStructure | None = None
                 ) -> tuple[str, int]:
    """The ONE place method/n0 defaults are resolved (pure host-side
    arithmetic, so cache keys are concrete).

    ``method="auto"`` dispatches through the Sec. VIII alpha-beta-gamma
    model — the fused comparison (``tuning.choose_method``) for
    one-shot solves, or the sweep-only steady comparison
    (``tuning.choose_serving_method``) when ``hoisted``: a resident
    factor pays phase 1 once at admission, so the inversion term must
    not count against "inv" in the per-solve dispatch.  An unset
    ``n0`` is consumed verbatim from the tuner's frozen
    :class:`~repro.core.tuning.TrsmPlan` for "inv" (``tune_for_grid``
    — or the hoisted-serving argmin ``serving_n0``), and set to the
    Sec. IV-A base-case size for "rec".

    ``structure`` (a :class:`~repro.core.structure.FactorStructure`)
    makes the hoisted dispatch and n0 argmin price exactly the blocks
    the level-scheduled sweep executes; the recursive alternative is
    priced dense (our recursion is structure-oblivious), so the
    comparison stays honest."""
    from repro.core import tuning
    if structure is not None and structure.is_dense:
        structure = None
    if method == "auto":
        if hoisted:
            method, h_n0, _ = tuning.choose_serving_method(
                n, k, grid, machine, n0=n0, structure=structure)
            if method == "inv" and n0 is None:
                n0 = h_n0
        else:
            method, _, _ = tuning.choose_method(n, k, grid.p, machine)
    if n0 is None:
        if method == "inv":
            n0 = tuning.serving_n0(n, grid, structure=structure) \
                if hoisted else \
                tuning.tune_for_grid(n, k, grid, machine).n0
        else:
            from repro.core import rec_trsm
            n0 = rec_trsm.default_n0(n, k, grid.p1, grid.p2)
    return method, n0


def _normalize_overlap(overlap) -> str | None:
    """Normalize an overlap request to its cache-key spelling.

    ``"off"``/``False``/``None`` -> ``None`` — byte-for-byte the key
    (and the program) pre-overlap specs always had, exactly like
    ``structure=dense -> None``.  ``"auto"``/``"on"``/``True`` ->
    ``"on"``: both methods support the pipelined sweep on every grid
    (degenerate meshes included — the prefetch degrades to the
    sequential issue order) and the result is bit-identical, so auto
    has no reason to ever resolve off (DESIGN.md Sec. 16)."""
    if overlap in (None, False, "off"):
        return None
    if overlap in (True, "auto", "on"):
        return "on"
    raise ValueError(f"overlap must be 'auto' | 'on' | 'off' | bool | "
                     f"None, got {overlap!r}")


# ------------------------------- SolveSpec -------------------------------

@dataclasses.dataclass(frozen=True)
class SolveSpec:
    """A frozen, hashable description of one solve configuration — and
    the sole :class:`~repro.core.session.CompiledSolverCache` key type.

    Field groups (the spec-field <-> cache-key table is DESIGN.md
    Sec. 10):

    * problem — ``n`` (factor order), ``k`` (RHS width; ``None`` marks
      a template spec that a :class:`Solver` completes per width),
      ``lower``/``transpose`` (the operator variant, DESIGN.md Sec. 3).
    * plan — ``method`` ("inv" | "rec"; ``"auto"`` is resolved BEFORE
      a spec exists, via :meth:`auto`), ``n0`` (diagonal-block size),
      ``mode`` (inv phase-1 scheme), ``grid`` (p1 x p1 x p2 placement;
      mesh identity is part of the key), ``block_inv`` (optional
      diagonal-inverter kernel hook).
    * execution — ``policy`` (the full
      :class:`~repro.core.precision.PrecisionPolicy`), ``bank_width``
      (``None`` = the unbanked one-shot program; M >= 1 = the batched
      program over an M-factor stack) and ``map_mode`` ("vmap" |
      "scan"; normalized to ``None`` when unbanked).
    * structure — the factor's
      :class:`~repro.core.structure.FactorStructure` (DESIGN.md
      Sec. 14).  ``None`` and ``FactorStructure.dense()`` are the SAME
      key (``__post_init__`` normalizes dense to ``None``), so a
      dense-structured spec compiles — and bit-identically runs — the
      exact program the unstructured path always has.
    * overlap — software pipelining of the steady-state sweep
      (DESIGN.md Sec. 16): ``"auto"`` (default) and ``"on"``/``True``
      normalize to ``"on"`` (prefetch panel j+1's collectives under
      panel j's compute); ``"off"``/``False`` normalize to ``None`` —
      the SAME cache key the pre-overlap specs always spelled, keying
      the bit-identical sequential-issue program (the
      structure-normalization discipline, applied again).

    Every field changes the compiled artifact, which is exactly why
    the spec is the cache key: two call sites that build equal specs
    share one compiled program, and nothing that matters can be left
    out of the key by accident.
    """
    n: int
    k: int | None
    grid: TrsmGrid
    policy: PrecisionPolicy
    method: str = "inv"
    n0: int | None = None
    mode: str | None = None
    lower: bool = True
    transpose: bool = False
    block_inv: Callable | None = None
    bank_width: int | None = None
    map_mode: str | None = None
    structure: FactorStructure | None = None
    overlap: str | bool | None = "auto"

    def __post_init__(self):
        if self.method not in ("inv", "rec"):
            raise ValueError(
                f"spec method must be 'inv' or 'rec', got {self.method!r}"
                f" (resolve 'auto' through SolveSpec.auto)")
        object.__setattr__(self, "overlap",
                           _normalize_overlap(self.overlap))
        if self.bank_width is not None and self.bank_width < 1:
            raise ValueError(f"bank width must be >= 1, got "
                             f"{self.bank_width}")
        if self.bank_width is None:
            object.__setattr__(self, "map_mode", None)
        elif self.map_mode is None:
            object.__setattr__(self, "map_mode", "vmap")
        if self.map_mode not in (None, "vmap", "scan"):
            raise ValueError(f"unknown map_mode {self.map_mode!r}")
        # dense IS the unstructured path: normalize so the two spell
        # the same cache key and compile the same (byte-identical)
        # program
        if self.structure is not None and self.structure.is_dense:
            object.__setattr__(self, "structure", None)

    # ------------------------------ queries ------------------------------

    @property
    def is_concrete(self) -> bool:
        """True when the spec can key a compiled program: shape and
        plan fully resolved, grid backed by a real mesh."""
        return (self.k is not None and self.n0 is not None
                and self.grid is not None
                and self.grid.mesh is not None)

    def with_k(self, k: int) -> "SolveSpec":
        """The same configuration at RHS width k."""
        return dataclasses.replace(self, k=k)

    def validate(self) -> "SolveSpec":
        """Check plan feasibility (raises ValueError): n0 must tile the
        factor (``n0 | n``) and, for "inv", respect the cyclic layout
        (``(p1*p2) | n0`` — each rank owns a contiguous slice of every
        diagonal block)."""
        n0 = self.n0
        if n0 is not None:
            if n0 < 1 or self.n % n0:
                raise ValueError(f"n0={n0} does not tile n={self.n}")
            if self.method == "inv" and self.grid is not None \
                    and n0 % (self.grid.p1 * self.grid.p2):
                raise ValueError(
                    f"n0={n0} infeasible for the cyclic layout on "
                    f"p1={self.grid.p1}, p2={self.grid.p2}")
        if self.structure is not None:
            self.structure.validate_for(self.n, lower=self.lower,
                                        transpose=self.transpose)
        return self

    # ---------------------------- construction ----------------------------

    @classmethod
    def auto(cls, n: int, k: int, *, grid: TrsmGrid | None = None,
             p: int | None = None, method: str = "auto",
             n0: int | None = None, mode: str | None = None,
             lower: bool = True, transpose: bool = False,
             machine=None, precision=None, dtype=None,
             block_inv: Callable | None = None,
             bank_width: int | None = None,
             map_mode: str | None = None,
             hoisted: bool | None = None,
             structure: FactorStructure | None = None,
             overlap: str | bool | None = "auto") -> "SolveSpec":
        """The a-priori front door: resolve the plan ONCE from the
        Sec. VIII cost model and freeze it into a spec.

        Pass either a ``grid`` (mesh pinned — n0/method tuned for it)
        or a processor count ``p`` (the tuner also picks p1/p2; the
        result carries a mesh-less :func:`plan_grid` and is a
        plan-only spec until re-targeted at a real mesh).  The tuner's
        frozen :class:`~repro.core.tuning.TrsmPlan` is consumed
        verbatim — same n0, same grid factors.  ``hoisted`` selects
        the serving-n0 argmin (defaults to True exactly when
        ``bank_width`` is set, i.e. when phase 1 runs at admission).
        ``precision`` accepts a preset name or PrecisionPolicy;
        ``dtype`` the legacy uniform policy; default fp32."""
        from repro.core import tuning
        if hoisted is None:
            hoisted = bank_width is not None
        if structure is not None and structure.is_dense:
            structure = None
        if structure is not None:
            structure.validate_for(n, lower=lower, transpose=transpose)
        if grid is None:
            if p is None:
                raise ValueError("SolveSpec.auto needs grid= or p=")
            if method == "auto":
                method, plan, _ = tuning.choose_method(n, k, p, machine)
            else:
                plan = tuning.tune(n, k, p, machine)
            grid = plan_grid(plan.p1, plan.p2)
            if n0 is None and method == "inv" and not hoisted:
                n0 = plan.n0                      # the plan, verbatim
        method, n0 = resolve_plan(grid, n, k, method=method, n0=n0,
                                  machine=machine, hoisted=hoisted,
                                  structure=structure)
        if precision is None and dtype is None:
            dtype = jnp.float32
        return cls(n=n, k=k, grid=grid,
                   policy=preclib.resolve(precision, dtype),
                   method=method, n0=n0, mode=mode, lower=lower,
                   transpose=transpose, block_inv=block_inv,
                   bank_width=bank_width, map_mode=map_mode,
                   structure=structure, overlap=overlap).validate()

    @classmethod
    def from_plan(cls, plan, *, k: int | None = None,
                  grid: TrsmGrid | None = None, precision=None,
                  dtype=None, mode: str | None = None,
                  lower: bool = True, transpose: bool = False,
                  block_inv: Callable | None = None,
                  bank_width: int | None = None,
                  map_mode: str | None = None) -> "SolveSpec":
        """Freeze a tuner-produced :class:`~repro.core.tuning.TrsmPlan`
        into a spec VERBATIM (method, n0, and grid factors are the
        plan's own).  ``grid`` may re-target the plan at a real mesh,
        but must agree with the plan's (p1, p2)."""
        if grid is None:
            grid = plan_grid(plan.p1, plan.p2)
        elif (grid.p1, grid.p2) != (plan.p1, plan.p2):
            raise ValueError(
                f"grid ({grid.p1}, {grid.p2}) does not match the "
                f"plan's ({plan.p1}, {plan.p2})")
        if precision is None and dtype is None:
            dtype = jnp.float32
        return cls(n=plan.n, k=plan.k if k is None else k, grid=grid,
                   policy=preclib.resolve(precision, dtype),
                   method=plan.method, n0=plan.n0, mode=mode,
                   lower=lower, transpose=transpose, block_inv=block_inv,
                   bank_width=bank_width, map_mode=map_mode).validate()


@dataclasses.dataclass(frozen=True)
class UpdateSpec:
    """A frozen, hashable description of one in-place bank update
    program — the second :class:`CompiledSolverCache` key type
    (DESIGN.md Sec. 11).

    Where a :class:`SolveSpec` keys the steady-state *solve* program,
    an UpdateSpec keys the *mutation* program: the single-factor
    admission pipeline (distribution gather + policy casts + hoisted
    phase 1) fused with a donated scatter into the bank's resident
    (C, ...) stacks.  Everything that changes the compiled artifact is
    a field: the factor order and plan, the precision policy (which
    roles exist and their dtypes), the operator variant (folded into
    the gather), the stack width C the scatter targets, and the
    ingestion layout (``"natural"`` runs the fused gather;
    ``"cyclic"`` takes a producer's working-layout factor and only
    casts).  Two same-shape banks share one compiled updater, and an
    updater never retraces across slots or occupancy changes.

    ``chunk`` widens the scatter to a CONTIGUOUS RUN of slots: the
    program takes a (chunk, n, n) stacked factor and writes slots
    ``start .. start + chunk - 1`` in one
    ``lax.dynamic_update_slice_in_dim`` — one dispatch where a per-slot
    loop would pay ``chunk`` (the ``refresh_banks`` stacked-parameter
    path).  ``pad_from`` declares the incoming factor is a smaller
    (d, d) order embedded into this bank's (n, n) bucket order: the
    program zero-pads rows/columns ``d..n-1`` and puts 1 on the padded
    diagonal (``blockdiag(L, I)`` in natural layout), so the padded
    tail solves to exact zeros against zero RHS rows and the leading
    d x k solution block is bit-identical to an unpadded order-d solve
    at the same n0 (DESIGN.md Sec. 12).
    """
    n: int
    grid: TrsmGrid
    policy: PrecisionPolicy
    method: str
    n0: int | None
    mode: str | None
    lower: bool
    transpose: bool
    block_inv: Callable | None
    bank_width: int              # C — the resident stack width
    ingest: str = "natural"      # "natural" | "cyclic"
    chunk: int = 1               # contiguous slots written per dispatch
    pad_from: int | None = None  # incoming factor order d (< n) or None
    structure: FactorStructure | None = None
    overlap: str | bool | None = None

    def __post_init__(self):
        if self.ingest not in ("natural", "cyclic"):
            raise ValueError(f"unknown ingest {self.ingest!r}")
        # the admission pipeline has no sweep to pipeline (phase 1's
        # doubling recurrence is serially dependent), so EVERY overlap
        # request normalizes to None: banks built with overlap on or
        # off share one compiled updater
        _normalize_overlap(self.overlap)       # validate the spelling
        object.__setattr__(self, "overlap", None)
        if self.structure is not None and self.structure.is_dense:
            object.__setattr__(self, "structure", None)
        if self.structure is not None:
            self.structure.validate_for(self.n, lower=self.lower,
                                        transpose=self.transpose)
            if self.ingest == "cyclic":
                raise ValueError(
                    "structured banks take natural ingestion only: the "
                    "admission mask is applied in natural layout, "
                    "before distribution")
        if self.bank_width < 1:
            raise ValueError(f"bank width must be >= 1, got "
                             f"{self.bank_width}")
        if not 1 <= self.chunk <= self.bank_width:
            raise ValueError(f"chunk must be in [1, bank_width="
                             f"{self.bank_width}], got {self.chunk}")
        if self.pad_from is not None:
            if not 1 <= self.pad_from < self.n:
                raise ValueError(f"pad_from must be in [1, n={self.n}), "
                                 f"got {self.pad_from}")
            if self.ingest == "cyclic":
                raise ValueError(
                    "pad_from requires natural ingestion (a cyclic "
                    "factor is already in the bucket-order storage "
                    "layout; zero-pad before distribution instead)")


def updater_for(uspec: UpdateSpec, cache=None):
    """Fetch (or build) the compiled in-place
    :class:`~repro.core.session.UpdaterProgram` for an update spec —
    the spec IS the cache key (same LRU as the solve programs)."""
    from repro.core import session
    if not isinstance(uspec, UpdateSpec):
        raise TypeError(f"updater_for takes an UpdateSpec, got "
                        f"{type(uspec).__name__}")
    session._check_policy_supported(uspec.policy)
    cache = cache if cache is not None else session.default_cache()
    return cache.get(uspec, lambda: session._build_updater(uspec))


def solver_for(spec: SolveSpec, cache=None):
    """Fetch (or build) the compiled
    :class:`~repro.core.session.SolverProgram` for a concrete spec —
    the spec IS the cache key."""
    from repro.core import session
    if not isinstance(spec, SolveSpec):
        raise TypeError(f"solver_for takes a SolveSpec, got "
                        f"{type(spec).__name__}")
    if not spec.is_concrete:
        raise ValueError(
            f"spec is not concrete (k={spec.k}, n0={spec.n0}, mesh="
            f"{'set' if spec.grid and spec.grid.mesh is not None else None}"
            f"): fill k/n0 and target a real mesh before compiling")
    session._check_policy_supported(spec.policy)
    cache = cache if cache is not None else session.default_cache()
    return cache.get(spec, lambda: session._build_solver(spec))


# -------------------------------- Solver --------------------------------

class Solver:
    """ONE serving class for resident triangular factors — any bank
    width, any precision policy, single- and multi-factor (DESIGN.md
    Sec. 10).

    A :class:`~repro.core.bank.FactorBank` is the admission layer: the
    factor(s) are distributed ONCE into stacked cyclic device storage
    (operator reductions folded into the gather, policy dtype casts,
    and — for method "inv" — phase 1, the paper's Diagonal-Inverter,
    hoisted so the steady state is the sweep alone).  A width-1 bank
    IS the single-factor case; there is no separate session type.

        solver = Solver.from_factor(L, grid, precision="bf16_refine")
        X = solver.solve(B)                   # B: (n, k) -> X: (n, k)

        solver = Solver.from_factors(Ls, grid)      # (M, n, n) stack
        X = solver.solve(Bs)                  # (M, n, k) in ONE dispatch

    ``solve`` accepts an (n, k) RHS when the width is 1 (returned in
    kind) or an (M, n, k) stack; after ``warmup`` the steady state
    performs zero host<->device transfers and zero retraces per RHS
    width, for every precision policy and every bank width (asserted
    in tests/test_api_solver.py at widths 1 and 16).

    Programs come from the :class:`CompiledSolverCache`, keyed by this
    solver's :meth:`spec_for` — same-width same-config solvers share
    one compiled program; factors are runtime operands, never baked-in
    constants.
    """

    def __init__(self, bank: FactorBank, *, cache=None):
        self.bank = bank
        self.cache = cache if cache is not None else bank.cache
        self.solves_served = 0
        self._served_program = None     # the program of the last solve

    # ---------------------------- constructors ----------------------------

    @classmethod
    def from_factor(cls, L, grid: TrsmGrid, *, method: str = "inv",
                    n0: int | None = None, mode: str | None = None,
                    lower: bool = True, transpose: bool = False,
                    machine=None, block_inv: Callable | None = None,
                    dtype=None, precision=None, map_mode: str = "vmap",
                    k_hint: int | None = None,
                    structure: FactorStructure | None = None,
                    overlap: str | bool | None = "auto",
                    cache=None) -> "Solver":
        """A width-1 solver around one natural-layout (n, n) factor
        (the former ``TrsmSession``).  ``method="auto"`` resolves the
        algorithm a priori from the cost model at ``k_hint`` RHS
        columns (default n); an unset n0 defaults to the
        hoisted-serving argmin (``tuning.serving_n0`` — phase 1 runs
        at admission, see DESIGN.md Sec. 9).  ``structure`` declares
        the factor's block structure (DESIGN.md Sec. 14): admission
        masks to it, the sweep skips outside it, and the n0 argmin
        prices it."""
        L = as_factor(L, dtype)
        if L.ndim != 2 or L.shape[0] != L.shape[1]:
            raise ValueError(f"factor must be square, got {L.shape}")
        n = L.shape[0]
        if method == "auto":
            method, n0 = resolve_plan(grid, n, k_hint or n,
                                      method="auto", n0=n0,
                                      machine=machine, hoisted=True,
                                      structure=structure)
        bank = FactorBank(grid, n, method=method, n0=n0, mode=mode,
                          lower=lower, transpose=transpose,
                          machine=machine, block_inv=block_inv,
                          dtype=None if precision is not None
                          else jax.dtypes.canonicalize_dtype(L.dtype),
                          precision=precision, map_mode=map_mode,
                          structure=structure, overlap=overlap,
                          cache=cache)
        bank.admit(L)
        return cls(bank, cache=cache)

    @classmethod
    def from_factors(cls, Ls, grid: TrsmGrid, *, method: str = "inv",
                     n0: int | None = None, mode: str | None = None,
                     lower: bool = True, transpose: bool = False,
                     machine=None, block_inv: Callable | None = None,
                     dtype=None, precision=None, map_mode: str = "vmap",
                     capacity: int | None = None,
                     structure: FactorStructure | None = None,
                     overlap: str | bool | None = "auto",
                     cache=None) -> "Solver":
        """A width-M solver over an (M, n, n) natural-layout stack,
        admitted in one stacked gather (the former bank construction +
        ``BatchedTrsmSession``).  ``capacity=C`` (>= M) allocates a
        LIVE-MUTABLE bank at width C: the compiled program is keyed on
        C, so later ``replace_factor``/``evict_factor``/``admit_factor``
        churn never retraces (DESIGN.md Sec. 11)."""
        Ls = as_factor(Ls, dtype)
        if Ls.ndim != 3 or Ls.shape[-1] != Ls.shape[-2]:
            raise ValueError(f"factor stack must be (M, n, n), got "
                             f"{Ls.shape}")
        bank = FactorBank(grid, Ls.shape[-1], method=method, n0=n0,
                          mode=mode, lower=lower, transpose=transpose,
                          machine=machine, block_inv=block_inv,
                          dtype=None if precision is not None
                          else jax.dtypes.canonicalize_dtype(Ls.dtype),
                          precision=precision, map_mode=map_mode,
                          capacity=capacity, structure=structure,
                          overlap=overlap, cache=cache)
        bank.admit_stack(Ls)
        return cls(bank, cache=cache)

    @classmethod
    def from_bank(cls, bank: FactorBank, *, cache=None) -> "Solver":
        """Serve an existing (possibly still-growing) FactorBank."""
        return cls(bank, cache=cache)

    @classmethod
    def from_spec(cls, spec: SolveSpec, factors=None, *,
                  capacity: int | None = None, cache=None) -> "Solver":
        """Spec-driven construction: build the admission bank from a
        spec's plan/execution fields and admit ``factors`` (one (n, n)
        factor or an (M, n, n) stack).  The spec's grid must carry a
        real mesh, and when the spec pins a ``bank_width`` the admitted
        factor count must match it — the spec is the cache key, so a
        width mismatch would silently key programs on a different spec
        than the one declared.  ``capacity`` (defaulting to the spec's
        ``bank_width`` when ``factors`` is omitted) allocates a
        live-mutable bank at the spec's width, to be filled by
        ``admit_factor``/``replace_factor`` later — the declarative
        churn-serving entry point."""
        if spec.grid is None or spec.grid.mesh is None:
            raise ValueError("spec has a plan-only grid; re-target it "
                             "at a real mesh (make_trsm_mesh) first")
        spec.validate()
        if capacity is None and factors is None:
            capacity = spec.bank_width
        if capacity is not None and spec.bank_width is not None \
                and capacity != spec.bank_width:
            raise ValueError(
                f"capacity={capacity} contradicts the spec's "
                f"bank_width={spec.bank_width} (the spec is the cache "
                f"key; the capacity IS the compiled width)")
        bank = FactorBank(spec.grid, spec.n, method=spec.method,
                          n0=spec.n0, mode=spec.mode, lower=spec.lower,
                          transpose=spec.transpose,
                          block_inv=spec.block_inv,
                          precision=spec.policy,
                          map_mode=spec.map_mode or "vmap",
                          capacity=capacity, structure=spec.structure,
                          overlap=spec.overlap, cache=cache)
        solver = cls(bank, cache=cache)
        if factors is not None:
            factors = jnp.asarray(factors)
            if factors.ndim == 3:
                bank.admit_stack(factors)
            else:
                bank.admit(factors)
        if spec.bank_width is not None and bank.width != spec.bank_width:
            raise ValueError(
                f"spec pins bank_width={spec.bank_width} but "
                f"{bank.size} factor(s) were admitted; pass a "
                f"matching stack (or a spec with bank_width=None)")
        return solver

    # ------------------------------ queries ------------------------------

    @property
    def n(self) -> int:
        return self.bank.n

    @property
    def width(self) -> int:
        """The bank WIDTH the compiled program is keyed on — the
        capacity of a capacity-allocated bank (occupancy changes never
        re-key; free slots ride along as inert zero lanes), else the
        live factor count (append-only: admitting grows the width and
        the next solve keys on it)."""
        return self.bank.width

    @property
    def occupancy(self) -> int:
        """The number of LIVE resident factors (<= width)."""
        return self.bank.size

    @property
    def grid(self) -> TrsmGrid:
        return self.bank.grid

    @property
    def policy(self) -> PrecisionPolicy:
        return self.bank.policy

    @property
    def dtype(self):
        """I/O dtype (what ``solve`` returns, what :meth:`place_rhs`
        casts to): residual dtype when the policy refines, compute
        dtype otherwise."""
        return self.bank.policy.io_dtype

    @property
    def method(self) -> str:
        return self.bank.method

    @property
    def n0(self) -> int | None:
        return self.bank.n0

    def spec_for(self, k: int) -> SolveSpec:
        """The concrete :class:`SolveSpec` (== cache key) serving RHS
        width k at the current bank width."""
        b = self.bank
        n0 = b.n0
        if n0 is None:                       # "rec" with unpinned n0
            from repro.core import rec_trsm
            n0 = rec_trsm.default_n0(b.n, k, b.grid.p1, b.grid.p2)
        return SolveSpec(n=b.n, k=k, grid=b.grid, policy=b.policy,
                         method=b.method, n0=n0, mode=b.mode,
                         lower=b.lower, transpose=b.transpose,
                         block_inv=b.block_inv, bank_width=b.width,
                         map_mode=b.map_mode, structure=b.structure,
                         overlap=b.overlap)

    def program_for(self, k: int):
        """The compiled :class:`~repro.core.session.SolverProgram` for
        RHS width k (built and cached on first use)."""
        return solver_for(self.spec_for(k), self.cache)

    # ------------------------------ serving ------------------------------

    def _lift(self, B):
        """Normalize an RHS to the (M, n, k) stack form; returns
        (stack, was_2d)."""
        if B.ndim == 2:
            if self.width != 1:
                raise ValueError(
                    f"rhs stack must be ({self.width}, {self.n}, k) for "
                    f"a width-{self.width} solver, got {B.shape}")
            if B.shape[0] != self.n:
                raise ValueError(f"rhs must be ({self.n}, k), got "
                                 f"{B.shape}")
            return jax.lax.expand_dims(B, (0,)), True
        if B.ndim != 3 or B.shape[0] != self.width \
                or B.shape[1] != self.n:
            raise ValueError(f"rhs stack must be ({self.width}, "
                             f"{self.n}, k), got {B.shape}")
        return B, False

    def place_rhs(self, B):
        """Pin an RHS — (n, k) at width 1, or an (M, n, k) stack — to
        the solve program's input sharding, in stack form.  A serving
        client that places requests as they arrive pays the
        (unavoidable) ingestion transfer up front; ``solve`` itself
        then moves no data at all."""
        B, _ = self._lift(jnp.asarray(B, self.dtype))
        prog = self.program_for(B.shape[-1])
        return jax.device_put(B, prog.rhs_sharding)

    def solve(self, B, *, donate: bool = True):
        """Solve op(L_i) X_i = B_i for every resident factor in ONE
        dispatch; X is returned in the rank B was given (an (n, k) RHS
        at width 1 yields an (n, k) X).  ``donate=True`` (serving
        semantics) donates the RHS buffer."""
        B, squeeze = self._lift(B)
        prog = self.program_for(B.shape[-1])
        if getattr(B, "sharding", None) != prog.rhs_sharding:
            # jit keys its trace on the input's sharding: an unplaced
            # panel and a place_rhs'd one would each trace the program
            B = jax.device_put(B, prog.rhs_sharding)
        fn = prog.solve_donating if donate else prog.solve
        X = self.bank.with_stacks(fn, B)
        self.solves_served += self.width
        self._served_program = prog
        # lax.squeeze, not X[0]: the getitem spelling lowers through
        # dynamic_slice, whose index operand is a host->device upload
        # on every call — it would break the zero-transfer steady state
        return jax.lax.squeeze(X, (0,)) if squeeze else X

    def warmup(self, k: int) -> "Solver":
        """Compile (and run once on zeros) the program for RHS width k
        at the current bank width, so the first real request is served
        at steady-state latency.  Also pre-runs the rank adapters
        (stack/slice) used by width-1 (n, k) serving.  A
        capacity-allocated bank can warm up EMPTY: the program is
        keyed on capacity, so it is already the one every later
        occupancy serves."""
        B = jnp.zeros((self.width, self.n, k), self.dtype)
        X = self.solve(B, donate=True)
        if self.width == 1:
            jax.lax.expand_dims(jnp.zeros((self.n, k), self.dtype),
                                (0,))                   # lift path
            jax.lax.squeeze(X, (0,))                    # squeeze path
        return self

    def stats(self) -> dict:
        """``solves_served``, and two counters of the solve program
        that served the last solve, taken once per program from its
        traced collectives (``SolverProgram.collectives``):
        ``collectives_per_solve``, the collectives one call issues
        over more than one device, and ``collective_words_per_col``,
        the words they move on the critical path (the paper's W) per
        right-hand-side column.  Both are 0 on a (1, 1) mesh and None
        before the first solve.  ``residual_tile_share``: the share of
        the dense refinement residual's multiply-adds that program
        does (``SolverProgram.residual_tile_share``; None also when
        the policy does not refine)."""
        prog = self._served_program
        count = words = share = None
        if prog is not None:
            cost = prog.collectives()
            count, words = cost.count, cost.w / prog.key.k
            share = prog.residual_tile_share
        return dict(solves_served=self.solves_served,
                    collectives_per_solve=count,
                    collective_words_per_col=words,
                    residual_tile_share=share)

    # ------------------------- live bank mutation -------------------------

    def admit_factor(self, L) -> int:
        """Admit one natural-layout (n, n) factor; returns its slot.
        On a capacity bank this fills (and re-uses) free slots in
        place — the compiled program does not change."""
        return self.bank.admit(L)

    def replace_factor(self, slot: int, L) -> int:
        """Refresh live ``slot`` in place with a new factor through
        the bank's compiled donated updater — zero retraces, zero host
        round trips, no rebuild (DESIGN.md Sec. 11)."""
        return self.bank.replace(slot, L)

    def evict_factor(self, slot: int) -> None:
        """Free live ``slot`` (capacity banks); the slot's lane goes
        inert until the next ``admit_factor`` re-uses it."""
        self.bank.evict(slot)

    def live_slots(self) -> tuple:
        """The live bank slots, ascending."""
        return self.bank.live_slots()


# ------------------------------ SolveServer ------------------------------

# StrandedRequestError now lives in the unified serving-error
# hierarchy (repro.core.errors, DESIGN.md Sec. 15); the historical
# spelling `repro.core.solver.StrandedRequestError` is a warn-once
# alias of the same class via __getattr__ below.

def __getattr__(name: str):
    if name == "StrandedRequestError":
        _warn_deprecated("repro.core.solver.StrandedRequestError",
                         "repro.api.StrandedRequestError "
                         "(repro.core.errors)")
        # warn-once: bind the module attribute so subsequent accesses
        # (and re-imports) resolve silently to the SAME class object
        globals()[name] = _errors.StrandedRequestError
        return _errors.StrandedRequestError
    raise AttributeError(f"module {__name__!r} has no attribute "
                         f"{name!r}")


@functools.lru_cache(maxsize=4096)
def static_slice(start: tuple, limit: tuple, squeeze: tuple = ()):
    """A jitted static slice (+ optional squeeze), cached per bounds.

    Op-by-op ``jax.lax.slice`` (and the ``X[f, :, a:b]`` getitem it
    underlies) ships its bounds as an int32 operand — one host->device
    upload per call, which breaks the zero-transfer steady state the
    serving tier asserts under ``jax.transfer_guard("disallow")``.
    Baking the bounds into a tiny jitted program moves that cost to a
    one-time compile; every subsequent call is a transfer-free
    dispatch.  Wave assembly/extraction cycles through a handful of
    layouts in steady state, so the cache stays tiny."""
    def run(A):
        out = jax.lax.slice(A, start, limit)
        return jax.lax.squeeze(out, squeeze) if squeeze else out
    return jax.jit(run)


def unit_wave_programs(width: int, n: int, panel_k: int, sharding):
    """The programs of a wave of single columns: ``(assemble, splits)``.

    ``assemble`` takes ``width x panel_k`` (n, 1) columns, slot-major,
    and returns the (width, n, panel_k) panel stack, on ``sharding``
    (the solve program's input sharding) where that is one device.
    On a mesh of several devices the stack is left on its columns'
    device: placed by the program, every column would be copied to
    every device, while :meth:`Solver.solve` moves only each device's
    shard of the stack.

    ``splits[m]`` returns the first ``m`` (n, 1) columns of each slot
    of a solved stack, slot-major, for ``m`` in 1, 2, 3, 4, 6, 8, 12,
    ... (powers of two and their halfway points) below ``panel_k``,
    and ``panel_k`` itself; a wave takes the smallest ``m`` its fill
    fits.  Each output is a device buffer the host makes and frees,
    at a cost per buffer, so a split of all ``panel_k`` columns would
    charge every wave for the full panel, and these sizes leave at
    most a third of a split's outputs unused."""
    place = {"out_shardings": sharding} \
        if len(sharding.device_set) == 1 else {}

    def assemble(*cols):
        # columns stacked as rows, then one transpose: on the TPU a
        # concatenate along axis 1 would first re-tile each (n, 1)
        # column into a padded (n, 128) buffer
        rows = jnp.stack([c.reshape(n) for c in cols])
        return rows.reshape(width, panel_k, n).transpose(0, 2, 1)

    def splitter(m):
        def split(X):
            # one transpose in row-major layout, then each column is a
            # contiguous row: on the TPU, a column taken straight from
            # X reads half of X's tiles
            rows = with_layout_constraint(jnp.swapaxes(X, 1, 2),
                                          Layout((0, 1, 2)))
            return tuple(rows[f, c].reshape(n, 1)
                         for f in range(width) for c in range(m))
        return split

    sizes = {s for i in range(panel_k.bit_length())
             for s in (1 << i, 3 << i >> 1) if s < panel_k}
    return (jax.jit(assemble, **place),
            {m: jax.jit(splitter(m)) for m in sorted(sizes | {panel_k})})


def _pack_wave(queue: collections.deque, panel_k: int) -> list:
    """First-fit pack one panel's worth of requests off the queue.

    Walks the whole queue in FIFO order and takes EVERY request that
    still fits in the remaining panel width (not just a contiguous
    head-of-line prefix): a wide request at the head no longer strands
    narrow requests behind it in an underfilled panel.  Skipped
    requests keep their relative order for the next wave.  Returns the
    packed [(seq, b), ...]; the queue keeps the rest."""
    wave: list = []
    width = 0
    leftover: collections.deque = collections.deque()
    while queue:
        seq, b = queue.popleft()
        if width + b.shape[1] <= panel_k:
            wave.append((seq, b))
            width += b.shape[1]
        else:
            leftover.append((seq, b))
    queue.extend(leftover)
    return wave


class SolveServer:
    """ONE continuous-batching front-end for a :class:`Solver` at any
    width (subsumes ``TrsmRequestServer`` and ``BankedTrsmServer``).

    Incoming solve requests (RHS column blocks of varying width,
    addressed to a bank factor — factor 0 is the whole bank at width
    1) are first-fit packed into fixed-width (n, panel_k) panels, one
    panel slot per factor, and every wave is ONE dispatch covering all
    factors: one executable for all traffic, zero retraces and zero
    host transfers in the steady state.  Factors with an empty queue
    ride along as zero panels (a solve of zeros is zeros, so idle
    factors never contaminate results and the program shape never
    changes); ``drain`` returns each factor's solutions in its own
    submit order.

        server = SolveServer(Solver.from_factors(Ls, grid), panel_k=16)
        server.warmup()
        server.submit(b, factor=2)
        outs = server.drain()          # {factor: [X, ...]}

    Constructed over a :class:`~repro.core.fleet.SolverFleet` instead
    of a Solver, the server routes submits by ``(tenant, order)``
    through the fleet's planner-chosen buckets (DESIGN.md Sec. 12):
    one lazy inner per-bucket server, the RHS zero-padded to the
    bucket order at submit, the solution sliced back to the request's
    true (d, j) at drain:

        server = SolveServer(fleet, panel_k=16)
        server.submit(b, tenant="modelA", tag="layer0")
        outs = server.drain()          # {(tenant, tag): [X, ...]}
    """

    def __init__(self, solver, panel_k: int):
        from repro.core.fleet import SolverFleet
        self.fleet = solver if isinstance(solver, SolverFleet) else None
        self.solver = None if self.fleet is not None else solver
        self.panel_k = panel_k
        if self.fleet is not None:
            # bucket key -> lazy inner server; (bucket key, slot) ->
            # FIFO of (tenant, tag, order) for slicing drained panels
            self._servers: dict = {}
            self._routes: dict = {}
        # lazily keyed by factor index, validated against the solver's
        # CURRENT width — factors admitted after server construction
        # are servable immediately (the next wave's program is simply
        # keyed on the new width)
        self._queues: dict[int, collections.deque] = {}
        self._seq = 0
        # slot generation at submit time, per request: a request must
        # never be served against a factor admitted after its slot was
        # evicted (re-admission makes the slot live again, so liveness
        # alone cannot catch it)
        self._req_gen: dict[int, int] = {}
        self._fillers: dict = {}     # dtype -> cached (n, panel_k) zeros
        self._unit: dict = {}        # bank width -> unit-wave programs
        self.requests_served = 0
        self.waves_solved = 0
        self.unit_waves_solved = 0

    @classmethod
    def from_spec(cls, spec: SolveSpec, factors, *, panel_k: int = 16,
                  cache=None, warm: bool = True) -> "SolveServer":
        """Spec-driven construction: admit ``factors`` under ``spec``
        and return a (warmed) server."""
        server = cls(Solver.from_spec(spec, factors, cache=cache),
                     panel_k=panel_k)
        return server.warmup() if warm else server

    @property
    def panels_solved(self) -> int:
        """Alias of ``waves_solved`` (a width-1 wave is one panel)."""
        return self.waves_solved

    def _server_for(self, key) -> "SolveServer":
        srv = self._servers.get(key)
        if srv is None:
            srv = self._servers[key] = SolveServer(
                self.fleet.solver(key), self.panel_k)
        return srv

    def submit(self, b, factor: int = 0, *, tenant: str | None = None,
               tag: object = None) -> None:
        """Enqueue one RHS block — an (n,) vector or (n, j) columns —
        for bank factor ``factor``.  Submits to an inactive (evicted /
        never-admitted) capacity slot are rejected: its lane is an
        inert zero panel, and solving real traffic against it would
        silently return garbage.

        In fleet mode the request is addressed by ``(tenant, order)``
        (+ ``tag`` when the tenant holds several factors of one
        order): the RHS row count IS the order, the fleet routes it to
        the planned bucket, and the panel is zero-padded to the bucket
        order (the padded factor's identity tail maps the zero rows to
        exact-zero solution rows)."""
        if self.fleet is not None:
            b = jnp.asarray(b)
            if b.ndim == 1:
                b = b[:, None]
            if b.ndim != 2:
                raise ValueError(f"rhs must be (d, j), got {b.shape}")
            h = self.fleet.lookup(tenant if tenant is not None
                                  else "default",
                                  order=int(b.shape[0]), tag=tag)
            n_b = h.bucket[0]
            if b.shape[0] < n_b:
                b = jnp.pad(b, ((0, n_b - b.shape[0]), (0, 0)))
            self._server_for(h.bucket).submit(b, factor=h.slot)
            self._routes.setdefault((h.bucket, h.slot),
                                    collections.deque()) \
                .append((h.tenant, h.tag, h.order))
            return
        if tenant is not None or tag is not None:
            raise ValueError("tenant=/tag= addressing needs a fleet "
                             "server (SolveServer(SolverFleet, ...))")
        if not 0 <= factor < self.solver.width:
            raise ValueError(f"unknown factor {factor}; bank holds "
                             f"{self.solver.width}")
        if not self.solver.bank.is_live(factor):
            raise ValueError(f"inactive slot {factor}: evicted or "
                             f"never admitted (live slots: "
                             f"{list(self.solver.live_slots())})")
        b = jnp.asarray(b, self.solver.dtype)
        if b.ndim == 1:
            b = b[:, None]
        if b.ndim != 2 or b.shape[0] != self.solver.n:
            raise ValueError(f"rhs must be ({self.solver.n}, j), "
                             f"got {b.shape}")
        if b.shape[1] > self.panel_k:
            raise ValueError(f"request wider than panel: {b.shape[1]} > "
                             f"{self.panel_k}")
        self._queues.setdefault(factor, collections.deque())
        self._req_gen[self._seq] = \
            self.solver.bank.slot_generation(factor)
        self._queues[factor].append((self._seq, b))
        self._seq += 1

    def pending(self) -> int:
        if self.fleet is not None:
            return sum(s.pending() for s in self._servers.values())
        return sum(len(q) for q in self._queues.values())

    def cancel(self, factor: int) -> int:
        """Drop every queued request for ``factor`` (and its bookkeeping);
        returns how many were dropped.  The recovery path when a slot
        was evicted with requests still pending: cancel the stranded
        slot, then ``drain`` serves the rest normally."""
        if self.fleet is not None:
            raise ValueError(
                "cancel is slot-addressed; a fleet server has no flat "
                "slot space (drain, or cancel on the bucket's own "
                "server)")
        q = self._queues.get(factor)
        if not q:
            return 0
        for seq, _ in q:
            self._req_gen.pop(seq, None)
        dropped = len(q)
        q.clear()
        # drop the dead key too, so pending()/drain stop iterating it
        self._queues.pop(factor, None)
        return dropped

    def _filler(self, dtype):
        """The all-zero (n, panel_k) panel idle factors ride along as —
        built ONCE per dtype and reused every wave, instead of
        reallocating per inactive slot per wave."""
        panel = self._fillers.get(dtype)
        if panel is None:
            panel = self._fillers[dtype] = \
                jnp.zeros((self.solver.n, self.panel_k), dtype)
        return panel

    def _unit_programs(self):
        """The unit layout's ``(assemble, splits, zero column)`` at the
        bank's current width (:func:`unit_wave_programs`), with the
        cached zero column the free places take.  Every program is
        compiled and run once here, at the first unit wave, so no
        later fill compiles anything."""
        width, n, pk = self.solver.width, self.solver.n, self.panel_k
        progs = self._unit.get(width)
        if progs is None:
            sharding = self.solver.program_for(pk).rhs_sharding
            assemble, splits = unit_wave_programs(width, n, pk, sharding)
            zero = jnp.zeros((n, 1), self.solver.dtype)
            stack = jax.device_put(assemble(*[zero] * (width * pk)),
                                   sharding)    # as the solve returns it
            for fn in splits.values():
                fn(stack)
            progs = self._unit[width] = (assemble, splits, zero)
        return progs

    def _solve_wave(self, waves: dict) -> dict:
        """Assemble and dispatch ONE wave: ``{slot: [(seq, b), ...]}``
        -> ``{slot: [(seq, X), ...]}``, packed order preserved, X the
        request's (n, j) column block.  Shared by :meth:`drain` (the
        synchronous caller-driven path) and the background drain loop
        of :class:`repro.core.serving.AsyncSolveServer`, which packs
        its own waves.

        When every request of the wave, in every slot, is one column
        wide, the wave takes the unit path: one cached program builds
        the panel stack from the requests' columns and a cached zero
        column in every free place, the solve runs, and one cached
        program splits off each slot's first ``m`` columns, ``m`` the
        smallest of a few fixed sizes the fill fits; the requests take
        theirs in packed order.  A wave then enqueues
        three device programs whatever its fill, and after the first
        unit wave no fill compiles anything new
        (:meth:`_unit_programs`).

        Every other layout (wider or mixed widths) concatenates each
        slot's requests with a slice of the cached zero filler, rides
        absent slots along as the filler itself, stacks, solves, and
        slices each request's block out with its own
        :func:`static_slice`: each concatenate, filler slice and
        result slice is a device program of its own, compiled per fill
        and offset.  All of them come from device arrays already held
        (a fresh ``jnp.pad``/getitem here would upload constants or
        indices on every wave), so the steady state stays
        transfer-free on either path.

        Host spans: ``trsm.wave.assemble`` (the panel stack's
        programs), ``trsm.wave.launch`` (the solve program's call) and
        ``trsm.wave.slice`` (the result programs).  On a busy chip the
        runtime can hold a call that enqueues a program for up to a
        wave."""
        if all(b.shape[1] == 1 for wave in waves.values()
               for _, b in wave):
            return self._solve_unit_wave(waves)
        n, pk = self.solver.n, self.panel_k
        panels = []
        with spans.span("wave.assemble"):
            for f in range(self.solver.width):
                wave = waves.get(f, ())
                if wave:
                    parts = [b for _, b in wave]
                    w = sum(b.shape[1] for b in parts)
                    if w < pk:
                        parts.append(static_slice((0, 0), (n, pk - w))(
                            self._filler(self.solver.dtype)))
                    panel = parts[0] if len(parts) == 1 \
                        else jnp.concatenate(parts, axis=1)
                else:
                    panel = self._filler(self.solver.dtype)
                panels.append(panel)
            B = jnp.stack(panels)
        with spans.span("wave.launch"):
            X = self.solver.solve(B)
        self.waves_solved += 1
        out: dict = {}
        with spans.span("wave.slice"):
            for f, wave in waves.items():
                off, xs = 0, []
                for seq, b in wave:
                    j = b.shape[1]
                    # jitted static slice, not X[f, :, off:...]: both
                    # the getitem spelling and op-by-op lax.slice
                    # upload their bounds as an int32 operand per wave
                    xs.append((seq, static_slice(
                        (f, 0, off), (f + 1, n, off + j), (0,))(X)))
                    off += j
                out[f] = xs
                self.requests_served += len(wave)
        return out

    def _solve_unit_wave(self, waves: dict) -> dict:
        """:meth:`_solve_wave` for a wave of single columns only."""
        assemble, splits, zero = self._unit_programs()
        pk = self.panel_k
        fill = max(len(wave) for wave in waves.values())
        m = min(s for s in splits if s >= fill)
        with spans.span("wave.assemble"):
            cols = [zero] * (self.solver.width * pk)
            for f, wave in waves.items():
                for c, (_, b) in enumerate(wave):
                    cols[f * pk + c] = b
            B = assemble(*cols)
        with spans.span("wave.launch"):
            X = self.solver.solve(B)
        self.waves_solved += 1
        self.unit_waves_solved += 1
        with spans.span("wave.slice"):
            xs = splits[m](X)
            out = {f: [(seq, xs[f * m + c])
                       for c, (seq, _) in enumerate(wave)]
                   for f, wave in waves.items()}
            self.requests_served += sum(len(w) for w in waves.values())
        return out

    def warmup(self) -> "SolveServer":
        if self.fleet is not None:
            self.fleet.warmup(self.panel_k)
            return self
        self.solver.warmup(self.panel_k)
        return self

    def drain(self) -> dict:
        """Serve all queued requests for all factors.  Returns
        {factor: [X, ...]} for every LIVE bank slot (empty list if
        none were queued; inactive capacity slots ride along as zero
        panels and are omitted), each factor's solutions in its own
        submit order.  Requests stranded on a slot that was evicted
        AFTER submission are an error — even if the slot was re-admitted
        since (a per-slot generation counter catches the turnover):
        their solutions would be garbage against whatever occupies the
        lane now.

        In fleet mode: drains every bucket's inner server and returns
        ``{(tenant, tag): [X, ...]}``, each solution sliced back to its
        request's true (d, j) — the padded tail rows are exact zeros
        and are dropped here."""
        if self.fleet is not None:
            results: dict[tuple, list] = {}
            for key, srv in self._servers.items():
                for slot, xs in srv.drain().items():
                    route = self._routes.get((key, slot))
                    for X in xs:
                        tenant, tag, d = route.popleft()
                        results.setdefault((tenant, tag), []).append(
                            X[:d, :] if d < X.shape[0] else X)
            self.requests_served = sum(s.requests_served
                                       for s in self._servers.values())
            self.waves_solved = sum(s.waves_solved
                                    for s in self._servers.values())
            return results
        pk = self.panel_k
        bank = self.solver.bank
        live = self.solver.live_slots()
        live_set = set(live)
        # a request is stale if its slot is gone OR was turned over
        # (evicted, even if re-admitted since) after it was submitted
        dead = sorted(f for f, q in self._queues.items() if q and (
            f not in live_set
            or any(self._req_gen[seq] != bank.slot_generation(f)
                   for seq, _ in q)))
        if dead:
            raise _errors.StrandedRequestError(
                f"pending requests for slot(s) {dead} evicted after "
                f"submission; drain before evicting a slot, or "
                f"cancel(factor) to drop the stranded requests")
        results: dict[int, dict] = {f: {} for f in live}
        while self.pending():
            waves = {f: _pack_wave(q, pk)
                     for f, q in self._queues.items() if q}
            for f, xs in self._solve_wave(waves).items():
                for seq, x in xs:
                    results[f][seq] = x
                    self._req_gen.pop(seq, None)
        return {f: [res[s] for s in sorted(res)]
                for f, res in results.items()}
