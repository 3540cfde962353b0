"""Compile rehearsal for the TPU: the served programs and the Pallas
kernels, compiled ahead of time for a DESCRIBED (not attached) v5e:2x2.

Nothing runs, so nothing here says anything about results or speed; it
catches what the chip's compiler would refuse (unsupported kernel ops,
programs that do not fit 16 GB of HBM) without spending chip time.
The topology is described inside a module fixture — never at import —
so every pytest-xdist worker collects the same tests and only the
worker given this file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import inv_trsm, session, tuning
from repro.core.grid import TrsmGrid
from repro.core.precision import PRESETS
from repro.core.solver import SolveSpec

N_CHIP = 32768     # chip_smoke.py's one-chip factor order
N_MESH = 2048      # factor order for the four-chip mesh programs
K = 128            # chip_smoke.py's panel width
HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _grid(topo, p1, p2):
    devs = np.asarray(topo.devices)[:p1 * p1 * p2].reshape(p1, p1, p2)
    return TrsmGrid(Mesh(devs, ("x", "y", "z")), p1, p2)


def _sds(grid, shape, dtype, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(grid.mesh, spec))


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used < HBM, used


@pytest.mark.parametrize("p1,p2,n", [(1, 1, N_CHIP), (1, 4, N_MESH),
                                     (2, 1, N_MESH)])
def test_served_programs_compile_for_v5e(topo, p1, p2, n):
    """Admission's phase-1 program, the sweep, and the refined solve
    program, at the plan the front door picks (``tuning.serving_n0``);
    on (2, 1), the mesh of the four-chip cell, also at its panel width
    4096."""
    ks = (K, 4096) if (p1, p2) == (2, 1) else (K,)
    grid = _grid(topo, p1, p2)
    n0 = tuning.serving_n0(n, grid)
    pol = PRESETS["bf16_refine"]
    mode = inv_trsm.pick_phase1_mode(n, n0, grid)
    ph1 = jax.jit(inv_trsm.it_inv_phase1_sharded(
        grid, n, n0, mode=mode, accum_dtype=pol.accumulate_dtype),
        out_shardings=NamedSharding(grid.mesh, inv_trsm.SPEC_DT))
    _fits(ph1.lower(_sds(grid, (n, n), pol.storage_dtype,
                         grid.spec_L())).compile())

    f32 = jnp.float32
    sweep = jax.jit(inv_trsm.it_inv_sweep_sharded(grid, n, K, n0,
                                                  accum_dtype=f32))
    _fits(sweep.lower(_sds(grid, (n, n), f32, grid.spec_L()),
                      _sds(grid, inv_trsm.dt_shape(n, n0), f32,
                           inv_trsm.SPEC_DT),
                      _sds(grid, (n, K), f32, grid.spec_B())).compile())

    lead = [P(None, *grid.spec_L()), P(None, *inv_trsm.SPEC_DT),
            P(None, *grid.spec_L())]
    shapes = [(1, n, n), (1,) + inv_trsm.dt_shape(n, n0), (1, n, n)]
    dts = [pol.storage_dtype, pol.storage_dtype, pol.residual_dtype]
    factor = tuple(_sds(grid, s, d, sp)
                   for s, d, sp in zip(shapes, dts, lead))
    for k in ks:
        prog = session._build_solver(SolveSpec(
            n=n, k=k, grid=grid, policy=pol, method="inv", n0=n0,
            bank_width=1))
        rhs = jax.ShapeDtypeStruct((1, n, k), pol.io_dtype,
                                   sharding=prog.rhs_sharding)
        _fits(prog.solve_donating.lower(factor, rhs).compile())


def test_phase1_on_2x2_needs_no_padded_copy(topo):
    """Phase 1 on mesh (2, 1) at the plan the front door picks: its
    scratch stays within a small multiple of its operands.  Assembling
    whole diagonal blocks by a reshape and transpose put a size-2 mesh
    axis minor-most, which the (8, 128) tiling pads 64-fold: 22x the
    operands here at n = 8192, and a 64 GiB copy at n = 65536."""
    grid = _grid(topo, 2, 1)
    n = 8192
    n0 = tuning.serving_n0(n, grid)
    pol = PRESETS["bf16_refine"]
    ph1 = session._build_phase1(grid, n, n0,
                                inv_trsm.pick_phase1_mode(n, n0, grid),
                                pol.accumulate_dtype, None)
    m = ph1.lower(_sds(grid, (n, n), pol.storage_dtype, grid.spec_L())
                  ).compile().memory_analysis()
    operands = m.argument_size_in_bytes + m.output_size_in_bytes
    assert m.temp_size_in_bytes <= 4 * operands, (m.temp_size_in_bytes,
                                                  operands)


def test_unit_wave_programs_compile_for_v5e(topo):
    """The front door's unit-wave programs at ``vec``'s size (n =
    32768, panel 256): the assemble needs no re-tiled copy of each
    column (4 GiB of scratch as a concatenate along the columns), and
    the split's outputs are compact (n, 1) columns."""
    from repro.core.solver import unit_wave_programs
    grid = _grid(topo, 1, 1)
    n, pk = N_CHIP, 256
    sharding = NamedSharding(grid.mesh, P())
    assemble, splits = unit_wave_programs(1, n, pk, sharding)
    assert sorted(splits) == [1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64,
                              96, 128, 192, 256]
    col = _sds(grid, (n, 1), jnp.float32, P())
    panel = n * pk * 4
    m = assemble.lower(*[col] * pk).compile().memory_analysis()
    assert m.output_size_in_bytes == panel
    assert m.temp_size_in_bytes < panel, m.temp_size_in_bytes
    stack = _sds(grid, (1, n, pk), jnp.float32, P())
    m = splits[pk].lower(stack).compile().memory_analysis()
    assert m.output_size_in_bytes < 2 * panel, m.output_size_in_bytes
    assert m.temp_size_in_bytes < panel, m.temp_size_in_bytes


def _kernel_call(name, n0):
    from repro.kernels import trmm, tri_inv_block, trsm_block
    f32 = jnp.float32
    if name == "trmm":
        return (lambda L, X: trmm.trmm(L, X, bt=n0, interpret=False),
                [((4 * n0, 4 * n0), f32), ((4 * n0, K), f32)])
    if name == "tri_inv_blocks":
        return (lambda L: tri_inv_block.tri_inv_blocks(L, interpret=False),
                [((4, n0, n0), f32)])
    return (lambda L, B: trsm_block.trsm_substitution(L, B,
                                                      interpret=False),
            [((4, n0, n0), f32), ((4, n0, K), f32)])


@pytest.mark.parametrize("n0", [128, 256])
@pytest.mark.parametrize("name", ["trmm", "tri_inv_blocks",
                                  "trsm_substitution"])
def test_pallas_kernel_compiles_for_v5e(topo, name, n0):
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    fn, args = _kernel_call(name, n0)
    compiled = jax.jit(fn).lower(
        *(jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in args)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
