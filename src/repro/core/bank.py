"""Multi-factor batched serving: FactorBank + BatchedTrsmSession
(DESIGN.md Sec. 9).

The paper's Sec. I pitch is that TRSM is the inner kernel of Cholesky /
LU / QR — real workloads solve against *many* triangular factors at
once (per-layer KFAC preconditioners, per-tenant models), not one.
A :class:`~repro.core.session.TrsmSession` serves one resident factor;
this module pools M of them:

* :class:`FactorBank` — a device-resident pool of M same-order
  triangular factors held as ONE stacked cyclic array (M, n, n),
  sharded ``P(None, "x", ("z", "y"))`` — the single-factor
  cyclic-storage contract (DESIGN.md Sec. 4) with a leading factor
  axis.  Admission runs the same fused distribution gather as a
  session (``grid.cyclic_matrix_device`` permutes the trailing two
  axes, so a whole (M, n, n) stack distributes in one program), and a
  refining precision policy keeps DUAL stacks (storage dtype for the
  sweep + residual dtype for the refinement GEMM), cast once at
  admission.  For the "inv" method admission ALSO runs phase 1 (the
  paper's Diagonal-Inverter) once per factor: the factors are
  immutable, so the inverted diagonal faces become resident state and
  the steady-state program is the sweep alone — which is why the
  bank's default n0 is the larger hoisted-serving argmin
  (``tuning.serving_n0``), not the session's fused-solve argmin.

* **Cyclic ingestion** — ``admit_cyclic`` accepts a factor ALREADY in
  cyclic storage, exactly what ``core.cholesky.cholesky_cyclic`` /
  ``core.lu.lu_cyclic`` produce: a factor computed on the grid enters
  the bank with zero host traffic and zero re-permutation (no
  unpermute -> re-permute round trip), closing the paper's
  factor-producer -> TRSM-consumer loop on device.

* **Live mutation** (DESIGN.md Sec. 11) — a bank built with
  ``capacity=C`` allocates its resident stacks at width C up front and
  becomes mutable in place: ``replace(slot, L)`` /
  ``replace_cyclic(slot, L_cyc)`` re-run the single-factor admission
  pipeline (gather + policy casts + hoisted phase 1) and scatter every
  factor role into the resident stacks through ONE compiled, donated
  updater program (cached in the :class:`CompiledSolverCache` under an
  :class:`~repro.core.solver.UpdateSpec`); ``evict(slot)`` frees a
  slot and ``admit`` re-uses freed slots.  The compiled solve program
  is keyed on C, not on occupancy, so churn — replace, evict, re-admit
  — never retraces and never rebuilds the bank.

* :class:`BatchedTrsmSession` — solves op(L_i) X_i = B_i for ALL i in
  one compiled program: the per-factor body (B-permute -> shard_map
  sweep -> X-unpermute -> unrolled refinement) is mapped over the
  factor axis with ``jax.vmap`` (every sweep step becomes an M-wide
  batched GEMM; the default) or ``jax.lax.scan`` (factors serialized
  inside the same single program; memory-lean for large M).  M
  per-layer or per-tenant solves cost ONE dispatch, and the
  single-session invariants extend verbatim: zero steady-state
  host<->device transfers and zero retraces for every precision policy
  (asserted in tests/test_factor_bank.py via
  :data:`repro.core.session.TRACE_COUNTS` + ``jax.transfer_guard``).

Programs come from the same :class:`CompiledSolverCache`; the bank
width M (and map mode) join the cache key, so two same-width banks of
the same configuration share one compiled program and the factors are
runtime operands, never baked-in constants.

Admission runs under the host span ``trsm.admit`` (``core/spans.py``),
which holds ``trsm.admit.ingest`` (the gather or the casts) and
``trsm.admit.phase1`` (phase 1's dispatch to the entry being ready)
where they are separate programs; a capacity bank's admission is one
updater program, under ``trsm.admit`` alone.
"""

from __future__ import annotations

import bisect
import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import precision as preclib
from repro.core import session as sessionlib
from repro.core import spans
from repro.core.grid import TrsmGrid
from repro.core.session import CompiledSolverCache, SolverProgram


def as_factor(L, dtype=None):
    """A factor as given: a device array stays put, anything else
    becomes a host NumPy array — never a default-device copy, which
    would sit on the chip next to the distributed copies for the whole
    admission (a chip-sized f32 factor is a quarter of its HBM)."""
    if isinstance(L, jax.Array):
        return L if dtype is None else L.astype(dtype)
    return np.asarray(L) if dtype is None else np.asarray(L, dtype)


# (...) -> (1, ...) with the source donated: XLA aliases the buffer, so
# a chip-sized factor is never on the device twice, as it is next to an
# eager ``a[None]`` copy.
_width1 = jax.jit(lambda a: a[None], donate_argnums=0)


class FactorBank:
    """A device-resident pool of M triangular factors in stacked cyclic
    storage, ready for one-dispatch batched solves.

        bank = FactorBank(grid, n=256, method="inv", n0=32,
                          precision="bf16_refine")
        for L in per_layer_factors:        # natural-layout (n, n)
            bank.admit(L)
        sess = BatchedTrsmSession(bank)
        X = sess.solve(B_stack)            # (M, n, k) in one dispatch

    All factors share one operator configuration (method, n0, lower,
    transpose, precision): the bank is a pool of *interchangeable*
    solves, which is what makes the single mapped program possible.

    ``dtype`` / ``precision`` follow :class:`TrsmSession` (a preset
    name or a PrecisionPolicy; default fp32 uniform).  ``map_mode``
    picks how the batched program maps the factor axis ("vmap" |
    "scan"); it is part of the compiled-program cache key.

    ``capacity=C`` allocates the resident stacks at width C up front
    (zero-filled slots solve to zeros — they never contaminate live
    lanes) and makes the bank LIVE-MUTABLE: ``admit`` fills the lowest
    free slot, ``replace``/``replace_cyclic`` refresh a live slot in
    place through one compiled donated scatter, and ``evict`` returns
    a slot to the free list.  The bank's *width* (what the compiled
    solve program is keyed on) is then C regardless of occupancy, so
    occupancy changes and per-slot churn never retrace (DESIGN.md
    Sec. 11).  Without ``capacity`` the bank is the classic append-only
    pool (width == size grows with each admission).
    """

    def __init__(self, grid: TrsmGrid, n: int, *, method: str = "inv",
                 n0: int | None = None, mode: str | None = None,
                 lower: bool = True, transpose: bool = False,
                 machine=None, block_inv: Callable | None = None,
                 dtype=None, precision=None, map_mode: str = "vmap",
                 capacity: int | None = None, structure=None,
                 overlap="auto",
                 cache: CompiledSolverCache | None = None):
        if precision is None and dtype is None:
            dtype = jnp.float32
        self.policy = preclib.resolve(precision, dtype)
        sessionlib._check_policy_supported(self.policy)
        if map_mode not in ("vmap", "scan"):
            raise ValueError(f"unknown map_mode {map_mode!r}")
        if method not in ("inv", "rec"):
            raise ValueError(f"bank method must be 'inv' or 'rec', got "
                             f"{method!r} (auto-dispatch is k-dependent; "
                             f"a bank's plan is fixed at admission)")
        # dense IS the unstructured bank (one cache key, one program)
        if structure is not None and structure.is_dense:
            structure = None
        if structure is not None:
            structure.validate_for(n, lower=lower, transpose=transpose)
        self.structure = structure
        # software pipelining of the steady-state sweep (DESIGN.md
        # Sec. 16): "auto" -> "on" (results are bit-identical either
        # way); "off"/None keys the pre-overlap program.
        from repro.core import solver as solverlib
        self.overlap = solverlib._normalize_overlap(overlap)
        self.grid = grid
        self.n = n
        self.method = method
        self.mode = mode
        self.lower = lower
        self.transpose = transpose
        self.machine = machine
        self.block_inv = block_inv
        self.map_mode = map_mode
        self.cache = cache if cache is not None \
            else sessionlib.default_cache()
        if method == "inv":
            # n0 is pinned at construction (admission pre-inverts the
            # diagonal blocks, so every program over this bank must
            # agree on the block size) — default: the hoisted-serving
            # argmin, which is LARGER than the session default because
            # the inversion cost leaves the steady state (DESIGN.md
            # Sec. 9 / tuning.serving_n0), and which prices the
            # structure's skipped blocks when one is declared
            # (Sec. 14).
            from repro.core import tuning
            self.n0 = n0 if n0 is not None else \
                tuning.serving_n0(n, grid, structure=structure)
            if n % self.n0 or self.n0 % (grid.p1 * grid.p2):
                raise ValueError(f"n0={self.n0} infeasible for n={n} on "
                                 f"p1={grid.p1}, p2={grid.p2}")
            from repro.core import inv_trsm
            self._phase1_mode = mode or inv_trsm.pick_phase1_mode(
                n, self.n0, grid)
        else:
            self.n0 = n0
            self._phase1_mode = None
        # resident cyclic copies: ``_stacks`` is the fused per-role
        # tuple of (width, ...) device arrays; ``_chunks`` holds
        # admitted-but-not-yet-fused chunks (tuples of per-role arrays
        # with a leading chunk axis).  stacks() fuses PENDING chunks
        # into the cached fused tuple incrementally — it never
        # re-concatenates the whole history, and a pool admitted as one
        # admit_stack IS its gather output.  Capacity-allocated banks
        # have no chunks at all: admission scatters into the
        # preallocated stacks through the compiled updater.
        self._chunks: list[tuple] = []
        self._size = 0
        self._stacks: tuple | None = None
        # held from reading the stacks to dispatching on them: an
        # updater donates the stacks it reads, so a solve must never
        # dispatch on a tuple an update has already consumed
        self._stacks_lock = threading.RLock()
        self._slot_ids: dict[int, object] = {}
        self._updaters: dict[tuple, object] = {}
        self.updates_dispatched = 0    # compiled scatter dispatches
        self.capacity = capacity
        if capacity is not None:
            if capacity < 1:
                raise ValueError(f"capacity must be >= 1, got {capacity}")
            self._live = [False] * capacity
            self._gens = [0] * capacity            # bumped per evict
            self._free = list(range(capacity))     # kept sorted, min-first
            # device-resident slot indices, pinned ONCE so steady-state
            # churn (replace/evict/admit) uploads nothing per update
            self._slot_ids = {i: self._place_slot_id(i)
                              for i in range(capacity)}
            self._stacks = self._alloc_stacks()
        else:
            self._live = None
            self._free = None

    # ------------------------------ admission ------------------------------

    @property
    def size(self) -> int:
        """M — the number of LIVE resident factors (occupancy)."""
        return self._size

    @property
    def width(self) -> int:
        """The resident stack width the compiled programs are keyed on:
        ``capacity`` for a capacity-allocated bank (occupancy changes
        never re-key), else the live size (append-only growth)."""
        return self.capacity if self.capacity is not None else self._size

    def __len__(self) -> int:
        return self.size

    def is_live(self, slot: int) -> bool:
        """Whether ``slot`` currently holds an admitted factor."""
        if self.capacity is None:
            return 0 <= slot < self._size
        return 0 <= slot < self.capacity and self._live[slot]

    def live_slots(self) -> tuple:
        """The live slot indices, ascending."""
        if self.capacity is None:
            return tuple(range(self._size))
        return tuple(i for i, live in enumerate(self._live) if live)

    def slot_generation(self, slot: int) -> int:
        """How many times ``slot`` has been TURNED OVER (evicted).  A
        server records this at submit time so a request can never be
        served against a factor admitted after its slot was evicted —
        ``replace`` deliberately does NOT bump it (refreshing a live
        factor in place is the intended serving semantic).  Append-only
        banks never turn slots over (always 0)."""
        return 0 if self.capacity is None else self._gens[slot]

    def _place_slot_id(self, slot: int):
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(jnp.asarray(slot, jnp.int32),
                              NamedSharding(self.grid.mesh,
                                            PartitionSpec()))

    def _roles(self) -> list:
        """(global shape, dtype, shard spec) per resident entry role:
        L_lo[, Dt][, L_hi]."""
        pol = self.policy
        roles = [((self.n, self.n), pol.storage_dtype,
                  self.grid.spec_L())]
        if self.method == "inv":
            from repro.core import inv_trsm
            roles.append((inv_trsm.dt_shape(self.n, self.n0),
                          pol.storage_dtype, inv_trsm.SPEC_DT))
        if pol.refines:
            roles.append(((self.n, self.n), pol.residual_dtype,
                          self.grid.spec_L()))
        return roles

    def _alloc_stacks(self) -> tuple:
        """Preallocate the (C, ...) resident stacks (zero-filled: a
        zero factor sweeps to a zero solution, so empty slots are
        inert lanes, never NaN sources for "inv")."""
        C = self.capacity
        return tuple(
            jax.device_put(jnp.zeros((C,) + shape, dt),
                           NamedSharding(self.grid.mesh, P(None, *spec)))
            for shape, dt, spec in self._roles())

    def _check_square(self, L, ndim: int, order: int | None = None) -> None:
        d = self.n if order is None else order
        if L.ndim != ndim or L.shape[-2:] != (d, d):
            lead = "(M, " if ndim == 3 else "("
            raise ValueError(f"factor must be {lead}{d}, {d}), "
                             f"got {L.shape}")

    def _resolve_pad(self, L, pad_to: int | None) -> int | None:
        """Normalize a padded-admission request: ``pad_to`` must name
        THIS bank's order (the bucket order the caller was routed to),
        the incoming factor a smaller (d, d).  Returns the UpdateSpec
        ``pad_from`` (None when d == n, i.e. no padding needed)."""
        if pad_to is None:
            return None
        if pad_to != self.n:
            raise ValueError(f"pad_to={pad_to} must equal the bank's "
                             f"order n={self.n} (route to the right "
                             f"bucket first)")
        if self.capacity is None:
            raise ValueError(
                "padded admission requires a capacity-allocated bank "
                "(FactorBank(..., capacity=C)): padding runs inside the "
                "compiled updater")
        d = int(L.shape[-1])
        if L.shape[-2:] != (d, d) or not 1 <= d <= self.n:
            raise ValueError(f"padded factor must be (d, d) with "
                             f"1 <= d <= {self.n}, got {L.shape}")
        return None if d == self.n else d

    def _phase1(self, L_lo, stacked: bool = False):
        """Admission-time phase 1: invert the factor's diagonal blocks
        ONCE (the paper's Diagonal-Inverter), so the steady-state
        program is the sweep alone."""
        ph1 = sessionlib._build_phase1(
            self.grid, self.n, self.n0, self._phase1_mode,
            self.policy.accumulate_dtype, self.block_inv, stacked)
        return ph1(L_lo)

    def _entry(self, parts: tuple, stacked: bool = False) -> tuple:
        """(L_lo[, L_hi]) -> the resident tuple (L_lo[, Dt][, L_hi])."""
        if self.method != "inv":
            return parts
        return (parts[0], self._phase1(parts[0], stacked)) + parts[1:]

    def admit(self, L, *, pad_to: int | None = None) -> int:
        """Distribute one natural-layout (n, n) factor into the bank
        (the session's fused gather, operator reductions folded in,
        diagonal blocks pre-inverted); returns the factor's bank
        slot.  A capacity-allocated bank fills its LOWEST free slot
        (re-using evicted slots) through the compiled in-place
        updater; an append-only bank grows by one.

        ``pad_to=n`` admits a SMALLER (d, d) factor into this bank's
        (n, n) bucket order: the compiled updater embeds it as
        ``blockdiag(L, I)`` so the inert tail solves to exact zeros and
        the leading d x k solution block is bit-identical to an
        unpadded order-d solve at the same n0 (DESIGN.md Sec. 12).
        Capacity banks only."""
        L = as_factor(L)
        pad_from = self._resolve_pad(L, pad_to)
        self._check_square(L, 2, order=pad_from)
        if self.capacity is not None:
            return self._admit_slot(L, "natural", pad_from=pad_from)
        with spans.span("admit"):
            preps = sessionlib._factor_preps(self.grid, self.lower,
                                             self.transpose, self.policy,
                                             structure=self.structure,
                                             n0=self.n0)
            with spans.span("admit.ingest"):
                if not isinstance(L, jax.Array):
                    # one upload for every prep (a refining policy has
                    # two)
                    L = jax.device_put(L, NamedSharding(self.grid.mesh,
                                                        P()))
                parts = tuple(p(L) for p in preps)
                del L
                # the upload is freed once the preps finish: wait for
                # that before phase 1 allocates its scratch next to
                # the copies
                jax.block_until_ready(parts)
            self._append(self._phase1_ready(parts))
        return self.size - 1

    def admit_stack(self, Ls):
        """Distribute a whole natural-layout (M, n, n) stack; returns
        the admitted slots (a range for append-only banks; a list for
        capacity banks, whose free slots may be non-contiguous).  An
        append-only bank (and an EMPTY capacity bank filled to exactly
        C) ingests the stack in ONE stacked gather program per dtype
        role (plus one stacked phase-1 inversion); a partially-filled
        capacity bank falls back to per-slot admission through the
        compiled updater."""
        Ls = as_factor(Ls)
        self._check_square(Ls, 3)
        M = Ls.shape[0]
        if self.capacity is not None:
            if M > len(self._free):
                raise ValueError(
                    f"bank full: {M} factors for {len(self._free)} free "
                    f"slot(s) of capacity {self.capacity} (evict first)")
            if self._size == 0 and M == self.capacity:
                # full-width fast path: the stacked gather output IS
                # the resident stack — no per-slot scatters at all
                with spans.span("admit"):
                    preps = sessionlib._factor_preps(
                        self.grid, self.lower, self.transpose,
                        self.policy, stacked=True,
                        structure=self.structure, n0=self.n0)
                    with spans.span("admit.ingest"):
                        parts = tuple(p(Ls) for p in preps)
                    entry = self._phase1_ready(parts, stacked=True)
                    self._stacks = tuple(
                        jax.device_put(a, NamedSharding(self.grid.mesh,
                                                        P(None, *spec)))
                        for a, spec in zip(entry, self._role_specs()))
                self._live = [True] * M
                self._free = []
                self._size = M
                return list(range(M))
            return [self.admit(Ls[j]) for j in range(M)]
        with spans.span("admit"):
            preps = sessionlib._factor_preps(self.grid, self.lower,
                                             self.transpose, self.policy,
                                             stacked=True,
                                             structure=self.structure,
                                             n0=self.n0)
            with spans.span("admit.ingest"):
                parts = tuple(p(Ls) for p in preps)
            stacks = self._phase1_ready(parts, stacked=True)
            first = self.size
            self._append_chunk(stacks, Ls.shape[0])
        return range(first, self.size)

    def admit_cyclic(self, L_cyc) -> int:
        """Direct cyclic ingestion: admit a factor ALREADY in the cyclic
        storage the producers emit (``cholesky_cyclic`` / ``lu_cyclic``
        outputs, or a session's ``factor_cyclic``) — no unpermute ->
        re-permute host round trip, no layout change at all; only the
        policy's dtype casts are applied (both resident copies when the
        policy refines, so pass the factor at residual precision or
        better).

        Only valid for the identity operator reduction (lower=True,
        transpose=False): for the other variants the distribution
        gather is not the plain cyclic map, so a raw cyclic array would
        be misinterpreted."""
        if not self.lower or self.transpose:
            raise ValueError(
                "cyclic ingestion requires lower=True, transpose=False "
                "(the reversal/transpose reductions are folded into the "
                "natural-layout distribution gather; a pre-permuted "
                "factor cannot carry them)")
        if self.structure is not None:
            raise ValueError(
                "cyclic ingestion into a structured bank is not "
                "supported: the admission-time block mask is applied "
                "in natural layout, before distribution (mask the "
                "factor yourself and use natural admission)")
        L_cyc = jnp.asarray(L_cyc)
        self._check_square(L_cyc, 2)
        if self.capacity is not None:
            return self._admit_slot(L_cyc, "cyclic")
        sharding = NamedSharding(self.grid.mesh, self.grid.spec_L())

        def cast(dt):
            # a copy: _append donates it, never the caller's buffer
            with spans.span("admit.ingest"):
                return jax.device_put(jnp.asarray(L_cyc, dt), sharding,
                                      may_alias=False)
        with spans.span("admit"):
            entry = self._phase1_ready((cast(self.policy.storage_dtype),))
            if self.policy.refines:
                # made once phase 1 has finished, so it never sits next
                # to phase 1's scratch (4 GiB each per chip at n =
                # 65536 on mesh (2, 1))
                entry += (cast(self.policy.residual_dtype),)
            self._append(entry)
        return self.size - 1

    def _phase1_ready(self, parts: tuple, stacked: bool = False) -> tuple:
        """:meth:`_entry` of the ingested ``parts``, waited for: the
        ``trsm.admit.phase1`` span, dispatch to the stacks being
        ready."""
        with spans.span("admit.phase1"):
            entry = self._entry(parts, stacked)
            jax.block_until_ready(entry)
        return entry

    def _append(self, entry: tuple) -> None:
        """Admit one factor: a chunk of width 1.  The entry's arrays
        are donated into it."""
        self._append_chunk(tuple(_width1(a) for a in entry), 1)

    def _append_chunk(self, stacks: tuple, count: int) -> None:
        with self._stacks_lock:
            self._chunks.append(stacks)
            self._size += count

    # ----------------------- live mutation (Sec. 11) -----------------------

    def _alloc_slot(self) -> int:
        if not self._free:
            raise ValueError(
                f"bank full: all {self.capacity} capacity slots are "
                f"live (evict one before admitting)")
        return self._free.pop(0)                  # lowest free slot

    def _admit_slot(self, L, ingest: str, pad_from: int | None = None) -> int:
        """Capacity admission: fill the lowest free slot through the
        compiled updater.  The slot is only committed once the scatter
        succeeds — a failed build/compile (or an interrupt during the
        updater's first trace) puts it back on the free list instead of
        leaking it."""
        slot = self._alloc_slot()
        try:
            with spans.span("admit"):
                self._scatter(slot, L, ingest, pad_from=pad_from)
        except BaseException:
            bisect.insort(self._free, slot)
            raise
        self._live[slot] = True
        self._size += 1
        return slot

    def _check_live(self, slot: int) -> None:
        if not 0 <= slot < self.width:
            raise ValueError(f"slot {slot} out of range for a "
                             f"width-{self.width} bank")
        if not self.is_live(slot):
            raise ValueError(f"slot {slot} is not live (evicted or "
                             f"never admitted); use admit to fill it")

    def update_spec(self, ingest: str = "natural", *, chunk: int = 1,
                    pad_from: int | None = None):
        """The frozen :class:`~repro.core.solver.UpdateSpec` keying
        this bank's compiled in-place updater (== its
        CompiledSolverCache / TRACE_COUNTS key)."""
        from repro.core import solver as solverlib
        if self.width < 1:
            raise ValueError("empty bank: admit factors before updating")
        return solverlib.UpdateSpec(
            n=self.n, grid=self.grid, policy=self.policy,
            method=self.method, n0=self.n0, mode=self._phase1_mode,
            lower=self.lower, transpose=self.transpose,
            block_inv=self.block_inv, bank_width=self.width,
            ingest=ingest, chunk=chunk, pad_from=pad_from,
            structure=self.structure)

    def _slot_id(self, slot: int):
        sid = self._slot_ids.get(slot)
        if sid is None:                  # append-only banks: pin lazily
            sid = self._slot_ids[slot] = self._place_slot_id(slot)
        return sid

    def _scatter(self, slot: int, L, ingest: str, *, chunk: int = 1,
                 pad_from: int | None = None) -> None:
        """Run the compiled donated updater: single-factor admission
        pipeline + scatter of every role into the resident stacks.
        The program is memoized per (ingest, width, chunk, pad_from) on
        the bank so the per-update host overhead is one dict probe, not
        an UpdateSpec construction + cache hash (width is in the key
        only for append-only banks, whose stacks grow; a capacity
        bank's width never changes)."""
        from repro.core import solver as solverlib
        memo = (ingest, self.width, chunk, pad_from)
        prog = self._updaters.get(memo)
        if prog is None:
            prog = solverlib.updater_for(
                self.update_spec(ingest, chunk=chunk, pad_from=pad_from),
                self.cache)
            self._updaters[memo] = prog
        # jit keys its trace on the input's sharding: a host factor and
        # a place_factor'd one would each trace the updater
        spec = P(*(None,) * L.ndim) if ingest == "natural" \
            else P(*(None,) * (L.ndim - 2), *self.grid.spec_L())
        sh = NamedSharding(self.grid.mesh, spec)
        if getattr(L, "sharding", None) != sh:
            L = jax.device_put(L, sh)
        with self._stacks_lock:
            self._stacks = prog.update(self.stacks(), self._slot_id(slot),
                                       L)
        self.updates_dispatched += 1

    def place_factor(self, L):
        """Pin a natural-layout replacement factor on device
        (replicated), so a subsequent :meth:`replace`/:meth:`admit`
        pays the (unavoidable) ingestion upload HERE and the update
        itself moves no host data — the factor-side analogue of
        ``Solver.place_rhs``."""
        return jax.device_put(jnp.asarray(L),
                              NamedSharding(self.grid.mesh,
                                            P(None, None)))

    def replace(self, slot: int, L, *, pad_to: int | None = None) -> int:
        """Refresh live ``slot`` IN PLACE with a new natural-layout
        (n, n) factor: one compiled program re-runs the admission
        pipeline for this factor alone (fused distribution gather +
        policy dtype casts + hoisted phase-1 inversion for "inv") and
        scatters all factor roles into the resident stacks with the
        stack buffers donated — zero retraces, zero host round trips,
        no re-stacking, no occupancy change (DESIGN.md Sec. 11).
        ``pad_to=n`` refreshes with a smaller (d, d) factor embedded as
        ``blockdiag(L, I)``, exactly as :meth:`admit`.  Returns the
        slot.  The host span ``trsm.replace`` covers the call: the
        updater lookup, the placement and the updater's dispatch (the
        call returns before the device has finished)."""
        with spans.span("replace"):
            L = L if isinstance(L, jax.Array) else jnp.asarray(L)
            pad_from = self._resolve_pad(L, pad_to)
            self._check_square(L, 2, order=pad_from)
            self._check_live(slot)
            self._scatter(slot, L, "natural", pad_from=pad_from)
        return slot

    def replace_run(self, start: int, Ls, *, pad_to: int | None = None
                    ) -> range:
        """Refresh a CONTIGUOUS RUN of live slots
        ``start .. start + u - 1`` with a stacked (u, d, d) factor
        batch in ONE compiled dispatch (``UpdateSpec.chunk = u``):
        stacked gather + stacked phase 1 + a single
        ``dynamic_update_slice`` into the donated resident stacks —
        where a per-slot loop would pay u dispatches
        (the ``refresh_banks`` stacked-parameter path, DESIGN.md
        Sec. 11).  Capacity banks only.  Returns the refreshed slot
        range."""
        if self.capacity is None:
            raise ValueError(
                "replace_run requires a capacity-allocated bank "
                "(FactorBank(..., capacity=C))")
        Ls = Ls if isinstance(Ls, jax.Array) else jnp.asarray(Ls)
        pad_from = self._resolve_pad(Ls, pad_to)
        self._check_square(Ls, 3, order=pad_from)
        u = int(Ls.shape[0])
        if u < 1:
            raise ValueError("replace_run needs at least one factor")
        for slot in range(start, start + u):
            self._check_live(slot)
        if u == 1:
            self._scatter(start, jax.lax.squeeze(Ls, (0,)), "natural",
                          pad_from=pad_from)
        else:
            self._scatter(start, Ls, "natural", chunk=u,
                          pad_from=pad_from)
        return range(start, start + u)

    def replace_cyclic(self, slot: int, L_cyc) -> int:
        """:meth:`replace` for a factor ALREADY in cyclic storage (a
        ``cholesky_cyclic``/``lu_cyclic`` producer output): the updater
        skips the distribution gather and only applies the policy's
        dtype casts (plus phase 1).  Same restriction as
        :meth:`admit_cyclic`: lower=True, transpose=False only."""
        if not self.lower or self.transpose:
            raise ValueError(
                "cyclic ingestion requires lower=True, transpose=False "
                "(the reversal/transpose reductions are folded into the "
                "natural-layout distribution gather; a pre-permuted "
                "factor cannot carry them)")
        L_cyc = L_cyc if isinstance(L_cyc, jax.Array) \
            else jnp.asarray(L_cyc)
        self._check_square(L_cyc, 2)
        self._check_live(slot)
        self._scatter(slot, L_cyc, "cyclic")
        return slot

    def evict(self, slot: int) -> None:
        """Return live ``slot`` to the free list (capacity banks only:
        an append-only bank has no slot lifecycle).  The slot's stale
        device data stays resident but inert — it is never solved
        against (servers zero its panel) and the next ``admit``
        overwrites it in place."""
        if self.capacity is None:
            raise ValueError(
                "evict requires a capacity-allocated bank "
                "(FactorBank(..., capacity=C)); append-only banks have "
                "no free slots")
        self._check_live(slot)
        self._live[slot] = False
        self._gens[slot] += 1
        bisect.insort(self._free, int(slot))
        self._size -= 1

    # ------------------------------- storage -------------------------------

    def _role_specs(self) -> list:
        """Per-role shard specs of a resident entry: L_lo[, Dt][, L_hi]."""
        specs = [self.grid.spec_L()]
        if self.method == "inv":
            from repro.core.inv_trsm import SPEC_DT
            specs.append(SPEC_DT)
        if self.policy.refines:
            specs.append(self.grid.spec_L())
        return specs

    def stacks(self) -> tuple:
        """The resident stacked arrays — one (width, ...) stack per
        factor role (sweep factor[, inverted diagonal faces][,
        residual-dtype factor]), each sharded with a leading unmapped
        factor axis.  Capacity banks return the preallocated stacks
        (admission/replace scattered into them in place — even an
        empty capacity bank has servable, zero-filled stacks, so a
        server can warm up BEFORE any factor exists).  Append-only
        banks fuse lazily and INCREMENTALLY: pending chunks are
        concatenated onto the cached fused stack — never a re-concat
        of the whole admission history per admission — and a pool
        admitted as one ``admit_stack`` IS its gather output
        (``jax.device_put`` onto the sharding it already has is
        free)."""
        with self._stacks_lock:
            return self._fused_stacks()

    def with_stacks(self, fn, *args):
        """``fn(stacks, *args)`` with no update of the stacks between
        reading them and the call: how a program that reads the
        resident stacks is dispatched while another thread may
        replace, evict or admit (an update donates the stacks it
        reads)."""
        with self._stacks_lock:
            return fn(self._fused_stacks(), *args)

    def _fused_stacks(self) -> tuple:
        if self._stacks is None and not self._chunks:
            raise ValueError("empty bank: admit factors before solving")
        if self._chunks:
            parts = ([self._stacks] if self._stacks is not None else []) \
                + self._chunks
            fused = parts[0] if len(parts) == 1 else tuple(
                jnp.concatenate([c[r] for c in parts])
                for r in range(len(parts[0])))
            self._stacks = tuple(
                jax.device_put(a,
                               NamedSharding(self.grid.mesh,
                                             P(None, *spec)))
                for a, spec in zip(fused, self._role_specs()))
            self._chunks = []
        return self._stacks

    @property
    def factors_cyclic(self):
        """The storage-dtype (M, n, n) stacked cyclic factor."""
        return self.stacks()[0]

    @property
    def factors_cyclic_residual(self):
        """The residual-precision (M, n, n) stacked copy (None unless
        the policy refines)."""
        return self.stacks()[-1] if self.policy.refines else None


class BatchedTrsmSession:
    """DEPRECATED multi-factor serving session — a thin shim over
    :meth:`repro.core.solver.Solver.from_bank`, kept for source
    compatibility; results are bit-identical to the
    :class:`~repro.core.solver.Solver` path.

    ``solve(B)`` takes an (M, n, k) stack — row i is the RHS panel for
    bank factor i — and returns the (M, n, k) solutions in one
    dispatch, with the usual steady-state invariants (zero transfers,
    zero retraces, every precision policy).  New code:

        solver = repro.api.Solver.from_bank(bank)   # or .from_factors
        X = solver.solve(B_stack)
    """

    def __init__(self, bank: FactorBank):
        from repro.core import solver as solverlib
        solverlib._warn_deprecated("BatchedTrsmSession",
                                   "Solver.from_bank")
        self._solver = solverlib.Solver.from_bank(bank)

    @classmethod
    def _wrap(cls, solver) -> "BatchedTrsmSession":
        self = object.__new__(cls)
        self._solver = solver
        return self

    @property
    def bank(self) -> FactorBank:
        return self._solver.bank

    @property
    def solves_served(self) -> int:
        return self._solver.solves_served

    @property
    def n(self) -> int:
        return self._solver.n

    @property
    def policy(self):
        return self._solver.policy

    @property
    def dtype(self):
        """The I/O dtype (what ``solve`` returns, what ``place_rhs``
        casts to): residual dtype for refining policies, compute dtype
        otherwise."""
        return self._solver.dtype

    def program_for(self, k: int) -> SolverProgram:
        return self._solver.program_for(k)

    def place_rhs(self, B):
        return self._solver.place_rhs(jnp.asarray(B, self.dtype))

    def solve(self, B, *, donate: bool = True):
        """Solve op(L_i) X_i = B_i for all M factors in one dispatch
        (strictly the (M, n, k) stack form, as before; M is the bank
        WIDTH — capacity for a capacity-allocated bank)."""
        M = self.bank.width
        if B.ndim != 3 or B.shape[0] != M or B.shape[1] != self.n:
            raise ValueError(f"rhs stack must be ({M}, {self.n}, k), "
                             f"got {B.shape}")
        return self._solver.solve(B, donate=donate)

    def warmup(self, k: int):
        self._solver.warmup(k)
        return self
