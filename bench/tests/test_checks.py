"""What decides ``correct``, driven through whole runs at the rehearsal
sizes on the CPU (the look for a chip skipped): sound runs are correct,
each cell's control is not, and neither is a run with the timed path
broken underneath in each way the cell can break."""

import argparse

import pytest

from bench import control, run as harness

CELLS = ["hplmxp_n32768.block", "hplmxp_n32768.vec", "kfac_granite8b.step"]


def _run(workload, seed=5, control_cfg=None):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.5,
                              trace=0, cpu_rehearsal=True, keep_trace=None)
    return harness.run(args, control=control_cfg)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = _run(workload, seed=2 ** 31 + 17)
    assert out["correct"], out["checked"]
    assert list(out["checked"])[-1] == "answers_compared"
    assert list(out)[-1] == "checked"


@pytest.mark.parametrize("workload,kind", [
    (w, k) for w in CELLS for k in control.kinds(w)])
def test_control_is_not_correct(workload, kind):
    for seed, out in control.readings(workload, 0.5, [7], kind, True):
        assert not out["correct"], out["checked"]


def test_a_patching_look_leaves_no_trace():
    """The residual look runs its patched program, and a run after it
    in one process runs the program's own again."""
    from repro.core import refine
    orig = refine.apply_cyclic_operator
    for seed, out in control.readings("hplmxp_n32768.block", 0.5, [7],
                                      "residual_high", True):
        assert out["checked"]["answers_compared"]["value"] > 0
    assert refine.apply_cyclic_operator is orig
    calls = []

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)
    refine.apply_cyclic_operator = spy
    try:
        out = _run("hplmxp_n32768.block", seed=7)
    finally:
        refine.apply_cyclic_operator = orig
    assert calls and out["correct"], out["checked"]


@pytest.mark.parametrize("workload", CELLS)
def test_answer_altered_where_produced(workload, monkeypatch):
    from repro.core.solver import Solver
    solve = Solver.solve

    def altered(self, B, **kw):
        X = solve(self, B, **kw)
        return X.at[..., 0, :].add(1.0)
    monkeypatch.setattr(Solver, "solve", altered)
    out = _run(workload)
    assert not out["correct"], out["checked"]


def test_refresh_leaves_the_bank_unchanged(monkeypatch):
    from repro.core.solver import Solver
    monkeypatch.setattr(Solver, "replace_factor", lambda self, s, L: s)
    out = _run("kfac_granite8b.step")
    assert not out["correct"], out["checked"]


def test_half_the_bank_left_out(monkeypatch):
    from repro.core.solver import Solver
    solve = Solver.solve

    def half(self, B, **kw):
        X = solve(self, B, **kw)
        return X.at[X.shape[0] // 2:].set(0.0) if X.ndim == 3 else X
    monkeypatch.setattr(Solver, "solve", half)
    out = _run("kfac_granite8b.step")
    assert not out["correct"], out["checked"]
