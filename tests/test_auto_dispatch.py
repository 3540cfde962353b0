"""method='auto' dispatch: the alpha-beta-gamma model instantiated with
machine constants picks the right algorithm per (shape, network)."""

import pytest

from repro.core import cost_model as cm, tuning


def test_auto_picks_rec_for_square_on_ici():
    m, _, t = tuning.choose_method(16384, 16384, 256, cm.tpu_v5e())
    assert m == "rec"
    assert t["rec"] < t["inv"]


def test_auto_picks_inv_for_small_k_on_ici():
    m, plan, t = tuning.choose_method(16384, 512, 256, cm.tpu_v5e())
    assert m == "inv"
    assert t["inv"] < t["rec"] / 3     # the paper's headline regime


def test_auto_picks_inv_on_dcn():
    m, _, t = tuning.choose_method(16384, 16384, 256, cm.tpu_v5e_dcn())
    assert m == "inv"


def test_auto_end_to_end_solve():
    import os
    # runs on 1 device: grid (1,1,1); auto still dispatches correctly
    import jax
    import numpy as np
    from repro import core
    from repro.core import grid as gridlib

    grid = gridlib.make_trsm_mesh(1, 1)
    rng = np.random.default_rng(0)
    n, k = 64, 16
    L = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    B = rng.standard_normal((n, k))
    X = core.trsm(L, B, grid, method="auto")
    np.testing.assert_allclose(X, np.linalg.solve(L, B), atol=5e-4)


def test_latency_improvement_scales_with_p():
    """The paper's S-advantage grows with p — auto flips to inv as the
    machine's alpha grows or p grows at fixed shape."""
    n, k = 1 << 15, 1 << 9
    adv = []
    for p in [64, 256, 1024]:
        rec = cm.rec_trsm_cost(n, k, p)
        plan = tuning.tune(n, k, p)
        adv.append(rec.s / plan.cost.s)
    assert adv[0] < adv[1] < adv[2]
