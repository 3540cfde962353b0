#!/usr/bin/env python3
"""Compile each cell's programs for a described (not attached) v5e:2x2
at the real sizes and print their HBM by ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/aot.py [workload ...]

Nothing runs: this says what the chip's compiler would refuse and how
many bytes each program needs, never a time.  The programs are the
ones a run builds: the factor generator, admission's gather (one per
resident dtype) and phase 1, the solve program at the mix's panel
width, and the bank's refresh program where the mix refreshes.
"""

from __future__ import annotations

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if sys.path and pathlib.Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402
import numpy as np                           # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa

from bench import data                       # noqa: E402
from bench.run import load_cell              # noqa: E402

GiB = 2 ** 30


def report(name, compiled):
    m = compiled.memory_analysis()
    print(f"  {name}: args {m.argument_size_in_bytes / GiB:.3f} GiB, "
          f"out {m.output_size_in_bytes / GiB:.3f} GiB, temp "
          f"{m.temp_size_in_bytes / GiB:.3f} GiB, alias "
          f"{m.alias_size_in_bytes / GiB:.3f} GiB (per device)", flush=True)


def cell(topo, workload):
    spec = load_cell(workload, False)
    cfg, mix = spec["cfg"], spec["mix"]
    if cfg["system"] == "kfac":
        kinds = cfg["factor_kinds"].values()
        groups = {}
        for shape in kinds:
            key = (shape["order"], shape["cols"])
            groups[key] = groups.get(key, 0) + cfg["num_hidden_layers"]
        for (n, k), m in groups.items():
            programs(topo, workload, cfg, n, m, k, refresh=True)
    else:
        programs(topo, workload, cfg, cfg["n"], cfg["capacity"],
                 mix["panel_k"], refresh=False)


def programs(topo, workload, cfg, n, width, k, refresh):
    from repro.core import inv_trsm, session, tuning
    from repro.core.grid import TrsmGrid
    from repro.core.precision import PRESETS
    from repro.core.solver import SolveSpec, UpdateSpec, solver_for
    p1, p2 = cfg["mesh"]
    devs = np.asarray(topo.devices)[:p1 * p1 * p2].reshape(p1, p1, p2)
    grid = TrsmGrid(Mesh(devs, ("x", "y", "z")), p1, p2)
    pol = PRESETS[cfg["precision"]]
    n0 = cfg.get("n0") or tuning.serving_n0(n, grid)
    mode = inv_trsm.pick_phase1_mode(n, n0, grid)
    print(f"{workload}: n={n} n0={n0} phase1={mode} mesh=({p1},{p2}) "
          f"bank={width} k={k} precision={cfg['precision']}", flush=True)

    def sds(shape, dtype, pspec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(grid.mesh, pspec))
    if cfg["system"] == "dense":
        word = jax.ShapeDtypeStruct((), jnp.uint32)
        if cfg.get("ingest", "natural") == "cyclic":
            gen = data.dense_factor_program(
                n, p1, p1 * p2, NamedSharding(grid.mesh, grid.spec_L()))
            report("factor generator (cyclic storage)",
                   gen.lower(word, word).compile())
            L = sds((n, n), jnp.float32, grid.spec_L())
            for dt in {pol.storage_dtype, pol.residual_dtype}:
                cast = jax.jit(lambda a, dt=dt: a.astype(dt),
                               out_shardings=NamedSharding(grid.mesh,
                                                           grid.spec_L()))
                report(f"cyclic ingestion cast to {jnp.dtype(dt).name}",
                       cast.lower(L).compile())
        else:
            rows = P(("x", "y", "z"), None)
            gen = data.dense_factor_program(
                n, 1, 1, NamedSharding(grid.mesh, rows))
            report("factor generator", gen.lower(word, word).compile())
            L = sds((n, n), jnp.float32, rows)
            for dt in {pol.storage_dtype, pol.residual_dtype}:
                prep = session._build_prep(grid, True, False, dt, False,
                                           None, n0)
                report(f"admission gather to {jnp.dtype(dt).name}",
                       prep.lower(L).compile())
    ph1 = session._build_phase1(grid, n, n0, mode, pol.accumulate_dtype,
                                None, False)
    report("phase 1", ph1.lower(
        sds((n, n), pol.storage_dtype, grid.spec_L())).compile())
    factor = [sds((width, n, n), pol.storage_dtype, P(None, *grid.spec_L())),
              sds((width,) + inv_trsm.dt_shape(n, n0), pol.storage_dtype,
                  P(None, *inv_trsm.SPEC_DT))]
    if pol.refines:
        factor.append(sds((width, n, n), pol.residual_dtype,
                          P(None, *grid.spec_L())))
    B = sds((width, n, k), pol.io_dtype, P(None, None, "z"))
    for transpose in (False, True) if refresh else (False,):
        sspec = SolveSpec(n=n, k=k, grid=grid, policy=pol, method="inv",
                          n0=n0, bank_width=width, overlap="on",
                          transpose=transpose)
        prog = solver_for(sspec)
        report(f"solve program k={k} transpose={transpose}",
               prog.solve.lower(tuple(factor), B).compile())
    if refresh:
        uspec = UpdateSpec(n=n, grid=grid, policy=pol, method="inv", n0=n0,
                           mode=mode, lower=True, transpose=False,
                           block_inv=None, bank_width=width,
                           ingest="natural", chunk=1, pad_from=None,
                           structure=None)
        upd = session._build_updater(uspec)
        slot = sds((), jnp.int32, P())
        report("refresh (updater)", upd.update.lower(
            tuple(factor), slot, sds((n, n), jnp.float32, P(None, None)))
            .compile())
        f = cfg["factor"]
        gen = data._kfac_program(n, f["tokens"], float(f["damping"]),
                                 NamedSharding(grid.mesh, P(None, None)))
        report("factor generator", gen.lower(
            jax.eval_shape(lambda: data.base_key(1, data.KFAC))).compile())


def main(argv):
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = argv or ["hplmxp_n32768.block", "hplmxp_n32768.vec",
                     "kfac_granite8b.step", "hplmxp_n65536_p4.block"]
    for w in names:
        try:
            cell(topo, w)
        except Exception as e:          # report and go on to the next
            print(f"  {w}: {type(e).__name__}: {str(e)[:600]}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
