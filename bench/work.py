"""The problem's work, whatever implements it, and the roofline.

A lower-triangular solve of order n with c right-hand-side columns
needs n^2 c flops (n^2/2 multiply-adds per column) and, at the least,
one read of the factor's lower half plus one read of B and one write of
X.  Only the columns callers asked for count; padding does not.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def solve_flops(n: int, cols: int, factors: int = 1) -> float:
    """W: factors x n^2 x useful columns."""
    return float(factors) * n * n * cols


def solve_bytes(n: int, cols: int, factor_bytes: int, io_bytes: int,
                factors: int = 1) -> float:
    """Q: each factor's lower half once, plus B read and X written."""
    return float(factors) * n * n / 2 * factor_bytes \
        + 2.0 * n * cols * io_bytes


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a device
    missing from the table is an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add its published numbers first")
    return table[device_kind]


def roofline(W: float, Q: float, device_s: float, peak: dict,
             chips: int):
    """(share of the roofline in %, which bound applied): the least
    time, max(W / flops peak, Q / bandwidth peak) with the peaks summed
    over the chips, over the device time taken."""
    t_flops = W / (peak["flops_per_s"] * chips)
    t_bytes = Q / (peak["bytes_per_s"] * chips)
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / device_s, bound
