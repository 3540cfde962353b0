"""The traffic loops, driven through ``load.drive`` by a mix's
``entry``, against a stand-in system that answers at once."""

import time

import jax  # noqa: F401  (imported before the window, as in a run)
import numpy as np
import pytest

from bench import load


class Done:
    def __init__(self, value):
        self.value = value
        self.completed = time.monotonic()

    def result(self, timeout=None):
        return self.value


class Server:
    def __init__(self):
        self.sent = []

    def submit(self, b):
        self.sent.append(b)
        return Done(np.full((2, 1), b))

    def rhs_for(self, i):
        return i, i % 5


@pytest.mark.parametrize("threads", [1, 3])
def test_async_server_counts_every_request_once(threads):
    system = Server()
    p = {"entry": "async_server", "callers": 8, "cols": 1,
         "client_threads": threads}
    sample = load.Reservoir(4, 7, lambda X: X)
    res = load.drive(system, p, 0.3, sample)
    assert res["attempted"] == len(system.sent) > 8
    assert sorted(system.sent) == list(range(res["attempted"]))
    assert res["failed"] == 0
    assert res["cols"] == len(res["latencies"]) == res["attempted"]
    assert len(sample.items) == 4 and sample.seen == res["attempted"]
    for (i, pidx), X in sample.items:
        assert pidx == i % 5 and X[0, 0] == i


class Bank:
    factors, versions, step_cols = 3, 2, 10

    def __init__(self):
        self.log = []

    def refresh(self, i, version):
        self.log.append((i, version))
        return [0.001, 0.002]

    def solve(self):
        return np.zeros(1)


def test_refresh_solve_walks_the_factors_round_robin():
    system = Bank()
    p = {"entry": "refresh_solve", "refresh_per_step": 1}
    sample = load.Reservoir(2, 7, lambda X: X)
    res = load.drive(system, p, 0.02, sample)
    steps = res["steps"]
    assert res["cols"] == 10 * steps and len(res["refresh_s"]) == 2 * steps
    assert system.log[:4] == [(0, 1), (1, 1), (2, 1), (0, 0)]
    assert all(len(holds) == 3 for holds, _ in sample.items)
