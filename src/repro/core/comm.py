"""Collective-communication shims with trace-time cost accounting.

Every distributed algorithm in ``repro.core`` issues its collectives
through this module.  Each wrapper (a) calls the corresponding
``jax.lax`` primitive unchanged and (b) — when a :class:`CostTrace` is
active — records the paper's alpha-beta-gamma cost of the call computed
from *static* shapes (Sec. II-C1 closed forms).  Because shapes are
static, the full critical-path cost of an algorithm is known at trace
time: tracing the program once (e.g. via ``jax.eval_shape``) yields the
exact S/W/F counts that the paper derives by hand.  This is the
mechanism behind ``benchmarks/bench_mm_costs.py`` and
``bench_paper_table.py`` (paper-table validation) and the collective
term of the roofline analysis.

Loop bodies are traced once but execute many times; wrap the loop in
``with comm.scope(trip_count):`` so recorded costs are multiplied by the
trip count (see ``inv_trsm.py``).

Cost conventions (paper Sec. II-C1, words = elements):
    allgather(n_total, p):      S = log p,   W = n_total * 1_p
    reduce-scatter(n_total, p): S = log p,   W = n_total * 1_p, F = n_total * 1_p
    allreduce(n, p):            S = 2 log p, W = 2 n * 1_p,     F = n * 1_p
    bcast(n, p):                S = 2 log p, W = 2 n * 1_p
    all-to-all(n_local, p):     S = log p,   W = n_local * log(p) / 2
    point-to-point (permute):   S = 1,       W = n_local
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro import compat


def _lg(p: float) -> float:
    return math.log2(max(p, 1.0))


def _ind(p: float) -> float:
    return 1.0 if p > 1 else 0.0


@dataclasses.dataclass
class Record:
    op: str
    axis: str
    p: int
    words: float      # payload measure used by the closed form (see op)
    s: float          # latency contribution (messages)
    w: float          # bandwidth contribution (words)
    f: float          # flop contribution
    mult: float       # loop multiplier in effect


@dataclasses.dataclass
class CostTrace:
    records: list[Record] = dataclasses.field(default_factory=list)

    @property
    def s(self) -> float:
        return sum(r.s * r.mult for r in self.records)

    @property
    def w(self) -> float:
        return sum(r.w * r.mult for r in self.records)

    @property
    def f(self) -> float:
        return sum(r.f * r.mult for r in self.records)

    @property
    def count(self) -> float:
        """Collectives issued over more than one device (a size-1 axis
        moves nothing)."""
        return sum(r.mult for r in self.records if r.p > 1)

    def by_op(self) -> dict:
        out: dict[str, dict] = {}
        for r in self.records:
            d = out.setdefault(r.op, dict(count=0.0, s=0.0, w=0.0, f=0.0))
            d["count"] += r.mult
            d["s"] += r.s * r.mult
            d["w"] += r.w * r.mult
            d["f"] += r.f * r.mult
        return out

    def summary(self) -> dict:
        return dict(s=self.s, w=self.w, f=self.f)


_ACTIVE: contextvars.ContextVar[CostTrace | None] = \
    contextvars.ContextVar("repro_comm_trace", default=None)
_MULT: contextvars.ContextVar[float] = \
    contextvars.ContextVar("repro_comm_mult", default=1.0)


@contextlib.contextmanager
def trace():
    """Activate cost recording; yields the CostTrace being filled."""
    t = CostTrace()
    tok = _ACTIVE.set(t)
    try:
        yield t
    finally:
        _ACTIVE.reset(tok)


@contextlib.contextmanager
def scope(mult: float):
    """Multiply costs recorded inside by ``mult`` (loop trip counts)."""
    tok = _MULT.set(_MULT.get() * mult)
    try:
        yield
    finally:
        _MULT.reset(tok)


def _axis_size(axis_name) -> int:
    return int(compat.axis_size(axis_name))


def _size(x) -> int:
    return int(math.prod(x.shape)) if x.shape else 1


def _rec(op, axis, p, words, s, w, f):
    t = _ACTIVE.get()
    if t is not None:
        name = ",".join(axis) if isinstance(axis, (tuple, list)) else str(axis)
        t.records.append(Record(op, name, p, words, s, w, f, _MULT.get()))


# --------------------------- the wrappers ---------------------------

def all_gather(x, axis_name, *, axis: int = 0, tiled: bool = False):
    p = _axis_size(axis_name)
    n_total = _size(x) * p
    _rec("allgather", axis_name, p, n_total,
         s=_lg(p), w=n_total * _ind(p), f=0.0)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def psum(x, axis_name):
    p = _axis_size(axis_name)
    n = _size(x)
    _rec("allreduce", axis_name, p, n,
         s=2 * _lg(p), w=2 * n * _ind(p), f=n * _ind(p))
    return jax.lax.psum(x, axis_name)


def psum_scatter(x, axis_name, *, scatter_dimension: int = 0,
                 tiled: bool = False):
    p = _axis_size(axis_name)
    n_total = _size(x)          # input holds the full (pre-scatter) array
    _rec("reduce-scatter", axis_name, p, n_total,
         s=_lg(p), w=n_total * _ind(p), f=n_total * _ind(p))
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=tiled)


def all_to_all(x, axis_name, *, split_axis: int, concat_axis: int,
               tiled: bool = False):
    p = _axis_size(axis_name)
    n_local = _size(x)
    _rec("alltoall", axis_name, p, n_local,
         s=_lg(p), w=n_local * _lg(p) / 2.0, f=0.0)
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)


def ppermute(x, axis_name, perm: Sequence[tuple[int, int]]):
    p = _axis_size(axis_name)
    n_local = _size(x)
    _rec("permute", axis_name, p, n_local, s=1.0, w=n_local, f=0.0)
    return jax.lax.ppermute(x, axis_name, perm)


# ------------------- async (start/finish) wrappers -------------------
#
# Software-pipelining primitives (DESIGN.md Sec. 16): ``*_start``
# issues the collective and returns an opaque handle; ``*_finish``
# yields its value.  The COST is recorded once, at start — that is
# where the messages leave the wire — so a start/finish pair prices
# identically to the synchronous wrapper it replaces.  On jax builds
# with no async collective API (every 0.4.x), ``repro.compat`` issues
# the collective eagerly and finish is the identity: bit-identical
# values, with overlap left to XLA's latency-hiding scheduler (the
# data dependence between start and finish is the same either way).

def all_gather_start(x, axis_name, *, axis: int = 0,
                     tiled: bool = False):
    """Begin ``all_gather``; pair with :func:`all_gather_finish`."""
    p = _axis_size(axis_name)
    n_total = _size(x) * p
    _rec("allgather", axis_name, p, n_total,
         s=_lg(p), w=n_total * _ind(p), f=0.0)
    return compat.async_all_gather_start(x, axis_name, axis=axis,
                                         tiled=tiled)


def all_gather_finish(handle):
    """Complete an :func:`all_gather_start` (cost already recorded)."""
    return compat.async_all_gather_finish(handle)


def ppermute_start(x, axis_name, perm: Sequence[tuple[int, int]]):
    """Begin ``ppermute``; pair with :func:`ppermute_finish`."""
    p = _axis_size(axis_name)
    n_local = _size(x)
    _rec("permute", axis_name, p, n_local, s=1.0, w=n_local, f=0.0)
    return compat.async_ppermute_start(x, axis_name, perm)


def ppermute_finish(handle):
    """Complete a :func:`ppermute_start` (cost already recorded)."""
    return compat.async_ppermute_finish(handle)


def bcast_from(x, axis_name, root: int = 0):
    """Broadcast the value held at ``root`` along ``axis_name`` to all.

    Implemented as mask + psum (the standard SPMD idiom); accounted with
    the paper's bcast cost 2 log p latency, 2n bandwidth (allgather +
    scatter construction, Sec. II-C1) — NOT with the allreduce cost of
    the implementation idiom, since on TPU XLA pattern-matches this to a
    broadcast.
    """
    p = _axis_size(axis_name)
    n = _size(x)
    _rec("bcast", axis_name, p, n,
         s=2 * _lg(p), w=2 * n * _ind(p), f=0.0)
    idx = jax.lax.axis_index(axis_name)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return jax.lax.psum(masked, axis_name)


def axis_index(axis_name):
    return jax.lax.axis_index(axis_name)


# ---------------------- trace helpers ----------------------

def traced_cost(fn, *args, **kwargs) -> CostTrace:
    """Trace ``fn`` (typically a jitted shard_map program) on abstract
    values and return the recorded collective costs.  ``args`` may be
    ShapeDtypeStructs or concrete arrays (no compute happens)."""
    with trace() as t:
        jax.eval_shape(fn, *args, **kwargs)
    return t
