"""The fixed work count of one PageRank pass: the roofline's numerator.

It depends only on the graph's size and the number of rank columns,
whatever implements the pass: one int32 index per edge, each column's
rank read and written once, and the float32 inverse out-degree read
once.  A layout that streams more (padding, a second index per edge)
or less (an index narrower than 32 bits) does not change it; an index
encoding below 32 bits would need the benchmark itself to revise it.
"""
from __future__ import annotations

INDEX_BYTES = 4      # int32 edge index
VALUE_BYTES = 4      # float32 rank / inverse degree


def pass_bytes(n: int, m: int, columns: int = 1) -> int:
    """HBM bytes the least pass over ``n`` vertices, ``m`` edges and
    ``columns`` rank vectors has to move: 4m + 4n(2B + 1)."""
    return (INDEX_BYTES * m
            + VALUE_BYTES * n * (2 * columns + 1))


def roofline_pct(n: int, m: int, columns: int, pass_s: float,
                 hbm_bytes_per_s: float) -> float:
    """Share, in %, of the HBM roofline that a pass taking ``pass_s``
    seconds reaches: least time at peak bandwidth over the pass time."""
    return 100.0 * pass_bytes(n, m, columns) / hbm_bytes_per_s / pass_s
