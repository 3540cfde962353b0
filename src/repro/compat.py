"""The jax API seam — the single place that names the jax spellings the
codebase depends on (the installed jax is 0.9.0).

Every module that needs ``shard_map``, ``pcast``, ``axis_size``, the
async-collective split or ``AbstractMesh`` goes through this file, so
a jax upgrade is a one-file change.
"""

from __future__ import annotations

import math

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``.  ``check_vma`` stays on unless one program
    states why its output is replicated where the checker cannot
    prove it (see ``inv_trsm.it_inv_phase1_sharded``)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def pcast_varying(x, axes):
    """``jax.lax.pcast(x, axes, to="varying")``."""
    return jax.lax.pcast(x, axes, to="varying")


def out_struct_like(shape, dtype, like):
    """``ShapeDtypeStruct`` carrying ``like``'s varying manual axes, so a
    ``pallas_call`` output composes inside shard_map bodies."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def axis_size(axis_name) -> int:
    """``jax.lax.axis_size`` of a single name or a tuple of names."""
    names = axis_name if isinstance(axis_name, (tuple, list)) \
        else (axis_name,)
    return int(math.prod(jax.lax.axis_size(a) for a in names))


# jax 0.9.0 has no start/finish collective split (XLA performs it
# internally via its latency-hiding scheduler), so the ``async_*_start``
# shims below issue the collective eagerly and ``async_*_finish`` is the
# identity — the VALUES are identical either way, and the scheduler is
# still free to overlap the issued collective with any data-independent
# compute between start and finish (DESIGN.md Sec. 16).


def async_all_gather_start(x, axis_name, *, axis: int = 0,
                           tiled: bool = False):
    """Begin an all-gather; returns the handle for
    :func:`async_all_gather_finish` (here: the gathered value)."""
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def async_all_gather_finish(handle):
    """Complete an all-gather started by :func:`async_all_gather_start`."""
    return handle


def async_ppermute_start(x, axis_name, perm):
    """Begin a ppermute; same contract as the gather."""
    return jax.lax.ppermute(x, axis_name, perm=perm)


def async_ppermute_finish(handle):
    """Complete a ppermute started by :func:`async_ppermute_start`."""
    return handle


def abstract_mesh(axis_sizes, axis_names, **kw):
    """``AbstractMesh(axis_sizes, axis_names)``."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names), **kw)
