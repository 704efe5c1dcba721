"""Chip benchmark of PCPM PageRank (see ``bench/run.py``).

Everything that belongs to one deployment, one cell, one traffic kind
or one per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: one deployment (graph, PageRank
  semantics, serving parameters);
- ``cells/<workload>.json``: one cell (config name, traffic kind, the
  kind's parameters, the limits of its correctness check);
- ``traffic/<kind>.py``: one module per traffic kind;
- ``metrics/<metric>.py``: one reader per per-layer quantity, shared
  by the metrics named ``<metric>.<suffix>``.

The shared yardstick lives beside them: the device gate and peaks
(``harness.py``, ``peaks.json``), the on-device Kronecker generator
(``kron.py``), the float64 oracle (``oracle.py``), the trace reduction
(``trace.py``) and the fixed work count (``work.py``).
"""
