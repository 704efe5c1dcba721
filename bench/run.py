#!/usr/bin/env python3
"""Chip benchmark of PCPM PageRank: one run of one cell.

    python bench/run.py --workload graph500-22.batch --seed 7 \\
        --seconds 20 --trace 0

The cell is the ``workloads`` entry of ``BENCHMARK.json`` named by
``--workload``; its file ``bench/cells/<workload>.json`` names the
deployment (``bench/configs/``), the traffic kind
(``bench/traffic/<kind>.py``) and the limits of its correctness check.
The run

1. refuses to start unless JAX's first device is a TPU whose peaks are
   in ``bench/peaks.json`` and the cell's chips are there (exit 2, no
   result);
2. sets up: makes the configuration's graph (``bench/graph.py``; its
   arcs listed in an order drawn from ``--seed``), builds the plan
   through ``repro.open`` and warms up every program the window runs
   (``setup_s``, from the start of the process);
3. measures for ``--seconds``, with the profiler on under ``--trace 1``;
4. checks what the window produced against the float64 reference;
5. prints, as the last line of standard output, one JSON object:
   ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
   end-to-end metrics, or with ``--trace 1`` its per-layer ones),
   ``device``, with ``--trace 1`` ``breakdown``, and last ``checks``:
   each number compared beside its limit.  The same numbers are the
   last lines of standard error.

JAX's compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402
from bench.harness import log  # noqa: E402


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _profile(log_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def is_correct(verdict: dict) -> bool:
    """Every attempted answer came and every number is within its
    limit."""
    return (verdict["failed"] == 0 and verdict["attempted"] > 0
            and all(c["value"] <= c["limit"] for c in verdict["checks"]))


def run_cell(reg: harness.Registry, workload: str, seed: int,
             seconds: float, trace: bool, device: dict,
             t_start: float = T_START) -> dict:
    """Set up, measure and check one cell on the current JAX device
    (``device`` is what the gate found); returns the result object."""
    import jax
    cell = reg.cell(workload)
    config = reg.config(cell["config"])
    kind = reg.traffic(cell["kind"])
    ctx = types.SimpleNamespace(cell=cell, config=config, seed=seed,
                                log=log, span=_span)
    state = kind.setup(ctx)
    setup_s = time.perf_counter() - t_start
    log(f"bench: set-up {setup_s:.3f} s")

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            _profile(log_dir)
        try:
            win = kind.window(state, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        memory = harness.peak_memory_bytes()
        verdict = kind.check(state)
        summary = None
        if trace:
            from bench import trace as tr
            summary = tr.reduce(*tr.read_events(tr.find_xplane(log_dir)))
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    checks = verdict["checks"]
    correct = is_correct(verdict)
    metrics = {}
    if trace:
        r = types.SimpleNamespace(trace=summary, counters=win["counters"],
                                  peaks=device.get("peaks", {}))
        for m in reg.per_layer(workload):
            value = reg.reader(m["name"]).read(r)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(win["metrics"], setup_s=setup_s)
        for m in reg.end_to_end(workload):
            if m["name"] not in values:
                raise KeyError(f"traffic kind {cell['kind']!r} reports no "
                               f"{m['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    dev = {k: device[k] for k in ("platform", "kind", "count")}
    dev["memory_peak_bytes"] = memory
    result = {"correct": correct, "attempted": verdict["attempted"],
              "failed": verdict["failed"], "metrics": metrics,
              "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks}
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, gate=harness.tpu_gate, root: str = ROOT) -> int:
    args = parse(argv)
    reg = harness.Registry(root)
    chips = reg.workload(args.workload)["chips"]
    try:
        device = gate(chips)
    except harness.NoDevice as e:
        log(f"bench: {e}")
        return 2
    log(f"bench: device platform={device['platform']} "
        f"kind={device['kind']} count={device['count']}")
    harness.use_compile_cache(root)
    result = run_cell(reg, args.workload, args.seed, args.seconds,
                      bool(args.trace), device)
    log(f"correct {result['correct']}")
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r}) {ok}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
