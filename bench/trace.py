"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The traced run records one ``.xplane.pb``.  From it this module takes

- the device operations: every event on the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane, as ``(name, start_ns, end_ns)``;
- the benchmark's host spans: every event named ``bench.*`` on the
  host planes, written by ``jax.profiler.TraceAnnotation`` in the
  benchmark's own files around each call into the program.

``reduce`` clips both to the ``bench.window`` span and gives the busy
time (the union of the device-op intervals, averaged over devices),
the window's length, the device operations that took most time (ops
that hold others, such as a ``while``, are left out of that list; the
busy union counts them), and the idle gaps summed by the innermost
host span they fall in.
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
TOP = 10


@dataclasses.dataclass
class Summary:
    busy_s: float                      # union of op intervals, mean over devices
    window_s: float                    # length of the bench.window span
    device_ops: list                   # [[name, seconds]], most time first
    idle_gaps: list                    # [[host span, seconds]], most first
    devices: int

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def short_name(hlo: str) -> str:
    """``%fusion.36 fusion f32[67108864]`` from the HLO text the trace
    names an op by: instruction, opcode and shape without layout."""
    inst, eq, rest = hlo.partition(" = ")
    if not eq:
        return hlo
    if rest.startswith("("):                 # tuple-shaped: skip it
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{")[0]
    return f"{inst} {rest.strip().split('(')[0]} {shape}"


def leaves(events):
    """The events that hold no other event: a ``while`` spans every op
    of its body, which would count twice."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, start, end) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or not (nxt[1] < end and nxt[2] <= end):
            out.append((name, start, end))
    return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_events(path: str):
    """``(ops, spans)``: device ops keyed by device plane name, and the
    benchmark's host spans, each a list of ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                start = float(ev.start_ns)
                item = (ev.name, start, start + float(ev.duration_ns))
                if device:
                    ops.setdefault(plane.name, []).append(item)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append(item)
    return ops, spans


def merge(intervals, lo: float, hi: float):
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``:
    ``(covered length, gaps)`` with the uncovered pieces as gaps."""
    covered = 0.0
    gaps = []
    cursor = lo
    for start, end in sorted(intervals):
        start, end = max(start, lo), min(end, hi)
        if end <= start or end <= cursor:
            continue
        if start > cursor:
            gaps.append((cursor, start))
        covered += end - max(start, cursor)
        cursor = end
    if cursor < hi:
        gaps.append((cursor, hi))
    return covered, gaps


def _label(gap, spans) -> str:
    """The innermost host span holding the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for name, start, end in spans:
        if start <= mid <= end and (best is None
                                    or end - start < best[2] - best[1]):
            best = (name, start, end)
    return best[0] if best else "no bench span"


def reduce(ops: dict, spans: list) -> Summary:
    windows = [s for s in spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found "
                         f"{len(windows)}")
    _, lo, hi = windows[0]
    if not ops:
        raise ValueError("the trace holds no device operations")
    inner = [s for s in spans if s[0] != WINDOW]
    busy = 0.0
    per_op: dict[str, float] = {}
    idle: dict[str, float] = {}
    for events in ops.values():
        covered, gaps = merge([(s, e) for _, s, e in events], lo, hi)
        busy += covered
        for name, start, end in leaves(events):
            d = min(end, hi) - max(start, lo)
            if d > 0:
                name = short_name(name)
                per_op[name] = per_op.get(name, 0.0) + d
        for gap in gaps:
            label = _label(gap, inner)
            idle[label] = idle.get(label, 0.0) + gap[1] - gap[0]
    k = len(ops)

    def top(d):
        return [[name, v / k * 1e-9] for name, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return Summary(busy_s=busy / k * 1e-9, window_s=(hi - lo) * 1e-9,
                   device_ops=top(per_op), idle_gaps=top(idle),
                   devices=k)
