"""compression_r: the plan's compression ratio r, arcs per PCPM update
(the paper's table V; ``GraphPlan.compression_ratio``), which the
labeling of the graph sets.  The traffic kind counts it as
``compression_r``.  Moves ``solve_s``: the scatter and the reduce run
one element per update."""


def read(r):
    return r.counters.get("compression_r")
