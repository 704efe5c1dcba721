"""pass_ms: device milliseconds per power iteration: busy time in the
traced window over the iterations run in it (the traffic kind counts
them as ``passes``; for ``batch``, the fused loop of core/pagerank.py
over core/spmv.py)."""


def read(r):
    passes = r.counters.get("passes")
    if r.trace is None or not passes or r.trace.busy_s <= 0:
        return None
    return 1e3 * r.trace.busy_s / passes
