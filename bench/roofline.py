"""Operations and bytes of the evaluation kernels, reckoned from shapes.

What each kernel must do for `candidates` placements, counted at the
problem's real sizes (no tile padding: padding is work the kernel chose,
so it shows as a lower roofline share, not as credit):

  * Eq. 1 (`kernels/wirelength.py`): reads the gathered endpoint
    coordinates x1, y1, x2, y2 and the weight of every net, float32, and
    writes one float32 per candidate; per net two differences, two
    absolute values, their sum, the product with the weight, the square and
    the accumulation (8).
  * Eq. 2 (`kernels/bbox.py`): reads every block's x and y grouped by
    conv unit, float32, and writes one float32 per candidate; per unit a
    max and a min over its blocks for x and for y (4 (B - 1) compares),
    two differences, their sum and the running max over units (4 B).

The least time is the larger of operations over peak operations per second
and bytes over peak bytes per second (`bench/peaks.json`); the kernels are
elementwise float32 work on the vector unit, far below the ridge point, so
bytes bound them.
"""
from __future__ import annotations

from typing import Dict, Tuple

F32 = 4


def wirelength(candidates: int, nets: int) -> Tuple[float, float]:
    """(operations, bytes) of Eq. 1 over `candidates` placements."""
    flops = 8.0 * candidates * nets
    nbytes = F32 * candidates * (5.0 * nets + 1.0)
    return flops, nbytes


def maxbbox(candidates: int, units: int, blocks: int) -> Tuple[float, float]:
    """(operations, bytes) of Eq. 2 over `candidates` placements."""
    flops = 4.0 * candidates * units * blocks
    nbytes = F32 * candidates * (2.0 * units * blocks + 1.0)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: Dict
               ) -> Tuple[float, str]:
    """(seconds, which bound) of work on a chip with these peaks."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
