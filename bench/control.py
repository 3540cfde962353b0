#!/usr/bin/env python3
"""Readings that set a cell's limits: a control (one of the
configuration's ``controls``: the program's lower-precision path, or
the plain reference at the next precision down in the program's place)
or a look (``looks``: a variant read for the record, which need not
fail) and sound runs, over many seeds in one process.  The benchmark's
own runs never run this.

    python3 bench/control.py --workload hplmxp_n32768.block \\
        --seconds 4 --kind residual_high --control 101 102 103 \\
        --sound 201 202 203

Control seeds run first, then sound ones; a control that patches the
program undoes it and drops its compiled programs when its run ends.
Each seed prints one JSON line with the compared numbers; a control
reading must fail the limit and a sound one pass it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import run as harness       # noqa: E402


def kinds(workload: str) -> list:
    """The names of the cell's controls, each of which must read as
    not correct."""
    return list(harness.load_cell(workload, False)["cfg"]["controls"])


def variant(cfg: dict, kind: str) -> dict:
    """A control, or a look: a variant read for the record only."""
    return {**cfg.get("looks", {}), **cfg["controls"]}[kind]


def readings(workload: str, seconds: float, seeds, kind: str | None,
             rehearsal: bool = False):
    """Yield (seed, result object) of one short run per seed, under
    control ``kind`` (None: the program as configured)."""
    cfg = harness.load_cell(workload, rehearsal)["cfg"]
    for seed in seeds:
        args = argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=0,
                                  cpu_rehearsal=rehearsal, keep_trace=None)
        yield seed, harness.run(args, control=variant(cfg, kind)
                                if kind else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--kind", default=None,
                    help="which of the configuration's controls or "
                         "looks (default: the first control)")
    ap.add_argument("--control", type=int, nargs="*", default=[])
    ap.add_argument("--sound", type=int, nargs="*", default=[])
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    kind = args.kind or kinds(args.workload)[0]
    for label, seeds, k in (("control", args.control, kind),
                            ("sound", args.sound, None)):
        for seed, out in readings(args.workload, args.seconds, seeds, k,
                                  args.cpu_rehearsal):
            print(json.dumps({"kind": k or label, "seed": seed,
                              "correct": out["correct"],
                              "metrics": out["metrics"],
                              "checked": out["checked"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
