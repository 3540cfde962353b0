"""The ``collective_pct`` reader against the small recorded trace
(data/small_trace.json) and a synthetic one, counted by hand here."""

import json
import pathlib
import types

import pytest

from bench import trace
from bench.metrics import collective_pct

DATA = pathlib.Path(__file__).parent / "data" / "small_trace.json"


def _ctx(tr):
    return types.SimpleNamespace(trace=tr, window=trace.window(tr),
                                 notes=[])


def test_slowest_device_of_the_small_trace():
    # busy: TPU:0 6000 ns, TPU:1 7500 ns -> TPU:1; its all-reduce
    # [3000,6000] runs alone: 3000 of 7500 = 40% (TPU:0's 2000 of
    # 6000 is not read)
    assert collective_pct.read(_ctx(json.loads(DATA.read_text()))) \
        == pytest.approx(40.0)


def test_hidden_and_overlapping_collectives_count_once():
    # dev 1: busy [0,100] and [150,250] = 200 ns; collectives
    # all-reduce [20,60] and all-gather [40,90] overlap -> [20,90] =
    # 70, under a fusion all the while; collective-permute-done
    # [240,300] cut by the window at 250 -> 10.  80 of 200 = 40%.
    # dev 0 is busy 100 ns only.
    tr = {"host": [["bench.window", 0, 250]],
          "devices": {
              "/device:TPU:0": {"ops": [["fusion.1", 0, 100]],
                                "modules": []},
              "/device:TPU:1": {"ops": [
                  ["fusion.2", 0, 100],
                  ["%all-reduce.3 = f32[8] all-reduce(%x)", 20, 60],
                  ["all-gather.4", 40, 90],
                  ["fusion.5", 150, 250],
                  ["collective-permute-done.6", 240, 300]],
                  "modules": []}}}
    assert collective_pct.read(_ctx(tr)) == pytest.approx(40.0)


def test_one_device_or_no_trace_reads_nothing():
    tr = json.loads(DATA.read_text())
    ctx = _ctx(tr)
    ctx.trace = {"host": tr["host"],
                 "devices": {"/device:TPU:0": tr["devices"]["/device:TPU:0"]}}
    assert collective_pct.read(ctx) is None
    ctx.trace = None
    assert collective_pct.read(ctx) is None
