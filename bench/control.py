#!/usr/bin/env python3
"""The control of a cell's correctness check: the float64 reference's
iteration computed with its ranks stored in bfloat16 (the precision
below the float32 the configurations state), put in the program's
place and judged by the cell's own comparison.  It has to come out as
not correct; its readings set the upper end of each limit.

    python bench/control.py --workload graph500-22.batch --seeds 1 2 3

For each seed it makes the cell's graph, answers what a run of the
cell answers (the global ranks) with the control, and prints the
numbers compared beside their limits.  The benchmark's own
runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import graph as bench_graph  # noqa: E402
from bench import harness, oracle  # noqa: E402


def control_answers(cell: dict, config: dict, g):
    """``(answers, ref)`` in the form the cell's ``judge`` takes, with
    the control in the program's place."""
    if cell["kind"] != "batch":
        raise ValueError(f"no control for traffic kind {cell['kind']!r}")
    n, it = g.num_nodes, config["iterations"]
    kw = dict(damping=config["damping"], iterations=it)
    t = np.ones(n)
    ref = oracle.pagerank(oracle.transition(n, g.src, g.dst), t, **kw)
    return [(oracle.pagerank_bf16(n, g.src, g.dst, t, **kw), it)], ref


def read_control(reg: harness.Registry, workload: str, seed: int) -> list:
    """The control's readings of the cell's compared numbers."""
    cell = reg.cell(workload)
    config = reg.config(cell["config"])
    g = bench_graph.make_graph(config, seed, harness.log)
    answers, ref = control_answers(cell, config, g)
    kind = reg.traffic(cell["kind"])
    return kind.judge(answers, ref, config, cell["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    reg = harness.Registry(ROOT)
    import jax
    harness.log(f"control: device {jax.devices()[0].device_kind}")
    harness.use_compile_cache(ROOT)
    for seed in args.seeds:
        checks = read_control(reg, args.workload, seed)
        failed = [c["name"] for c in checks if c["value"] > c["limit"]]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fails": failed, "checks": checks}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
