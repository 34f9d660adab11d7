"""Statistics the benchmark reports, in plain Python."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0 < q < 100) by linear interpolation between
    closest ranks (numpy's default), over all values."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def kendall_tau_b(xs, ys) -> float | None:
    """Kendall's tau-b between two paired sequences; ties in either count
    as neither concordant nor discordant and shrink the denominator.  None
    where one side is all ties or there are fewer than two pairs."""
    n = len(xs)
    if n != len(ys):
        raise ValueError("paired sequences differ in length")
    conc = disc = ties_x = ties_y = 0
    for i in range(n):
        for j in range(i + 1, n):
            dx = xs[i] - xs[j]
            dy = ys[i] - ys[j]
            if dx == 0 and dy == 0:
                continue
            if dx == 0:
                ties_x += 1
            elif dy == 0:
                ties_y += 1
            elif (dx > 0) == (dy > 0):
                conc += 1
            else:
                disc += 1
    denom = math.sqrt((conc + disc + ties_x) * (conc + disc + ties_y))
    return (conc - disc) / denom if denom else None
