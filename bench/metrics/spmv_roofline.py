"""spmv_roofline: share, in %, of the HBM roofline that one pass
reaches: the fixed work bytes of a pass over the graph's ``n``, ``m``
and rank columns (bench/work.py) at the chip's peak bandwidth, over
the device busy time per pass read from the trace."""
from bench import work


def read(r):
    c = r.counters
    if r.trace is None or not c.get("passes") or r.trace.busy_s <= 0:
        return None
    return work.roofline_pct(c["n"], c["m"], c["columns"],
                             r.trace.busy_s / c["passes"],
                             r.peaks["hbm_bytes_per_s"])
