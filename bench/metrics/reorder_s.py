"""reorder_s: host seconds the plan build spent relabeling the graph
for locality (the permutation of graphs/reorder.py and the relabeled
arcs), from the program's own counter ``plan_cache_stats().reorder_s``
(core/plan.py, span ``repro.plan.reorder``; ``Session.stats()``
reports it under ``plan_cache``).  Read where the cell reports
``plan_build_s``, the ``repro.open`` it is part of.  A program that
keeps no such counter gives nothing.  Moves ``setup_s``."""


def read(r):
    if "plan_build_s" not in r.counters:
        return None
    from repro.core.plan import plan_cache_stats
    return getattr(plan_cache_stats(), "reorder_s", None)
