"""The problem's work and bytes against hand counts, and the peaks."""

import pytest

from bench import work


def test_solve_flops_by_hand():
    # order 4, 3 columns: 16 * 3 = 48; 24 factors of order 4096 at 4096
    # columns: 24 * 2**36 = 1649267441664
    assert work.solve_flops(4, 3) == 48
    assert work.solve_flops(4096, 4096, 24) == 1649267441664


def test_solve_bytes_by_hand():
    # order 4, f32: lower half 8 entries * 4 B = 32; B and X 2*4*3*4 = 96
    assert work.solve_bytes(4, 3, 4, 4) == 128
    assert work.solve_bytes(4, 3, 4, 4, factors=2) == 64 + 96


def test_roofline_names_its_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert work.roofline(200, 10, 4.0, peak, 1) == (50.0, "compute")
    assert work.roofline(10, 200, 40.0, peak, 1) == (50.0, "memory")
    assert work.roofline(200, 10, 1.0, peak, 2) == (100.0, "compute")


def test_peaks_table():
    assert work.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
