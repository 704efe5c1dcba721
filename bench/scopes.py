"""Device time by the program's named scopes, and idle time by the
program's host spans, from a JAX profile (``.xplane.pb``).

``bench/trace.py`` reads the profile through ``jax.profiler.ProfileData``,
which gives each device op its name and times but not its metadata.
The program names the phases of its pass with ``jax.named_scope``
(``pcpm.scatter``, ``pcpm.expand``, ``pcpm.reduce`` in core/spmv.py,
``pagerank.apply`` in core/pagerank.py), and XLA writes each op's
scope path into the device plane's event metadata as the ``tf_op``
stat (``jit(run)/while/body/jit(pcpm_scatter)/pcpm.scatter/gather:``).
This module decodes the profile itself, with protobuf and the fields
of TSL's public ``xplane.proto`` that it reads, and

- ``scope_seconds``: sums the time of the leaf device ops inside the
  ``bench.window`` span (clipped to it, ``while`` ops left out, as in
  ``trace.reduce``) by the innermost program scope of each op's
  ``tf_op``: the last path element of the form ``<word>.<word>``.
  Ops with none, such as copies XLA inserts, go under ``unscoped``.
- ``idle_by_span``: sums the window's idle gaps by the innermost host
  span around each, among the benchmark's ``bench.*`` spans and the
  program's ``repro.*`` phases.

Seconds are means over the device planes.  Run on a profile to print
both, per pass where ``--passes`` is given::

    python bench/scopes.py <dir>/plugins/profile/<run>/<host>.xplane.pb \\
        --passes 20
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

UNSCOPED = "unscoped"
SPAN_PREFIXES = ("bench.", "repro.")
PROGRAM_SCOPE = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")

# (message, [(number, field, type, repeated, message type)]) of
# tensorflow/tsl/profiler/protobuf/xplane.proto; a map is read as its
# repeated key/value entries, which is the same wire format
_I64, _U64, _STR, _F64, _MSG = "int64", "uint64", "string", "double", "msg"
_SCHEMA = [
    ("XSpace", [(1, "planes", _MSG, True, "XPlane")]),
    ("XPlane", [(2, "name", _STR, False, None),
                (3, "lines", _MSG, True, "XLine"),
                (4, "event_metadata", _MSG, True, "EventMetadataEntry"),
                (5, "stat_metadata", _MSG, True, "StatMetadataEntry"),
                (6, "stats", _MSG, True, "XStat")]),
    ("EventMetadataEntry", [(1, "key", _I64, False, None),
                            (2, "value", _MSG, False, "XEventMetadata")]),
    ("StatMetadataEntry", [(1, "key", _I64, False, None),
                           (2, "value", _MSG, False, "XStatMetadata")]),
    ("XLine", [(2, "name", _STR, False, None),
               (3, "timestamp_ns", _I64, False, None),
               (4, "events", _MSG, True, "XEvent")]),
    ("XEvent", [(1, "metadata_id", _I64, False, None),
                (2, "offset_ps", _I64, False, None),
                (3, "duration_ps", _I64, False, None)]),
    ("XStat", [(1, "metadata_id", _I64, False, None),
               (2, "double_value", _F64, False, None),
               (3, "uint64_value", _U64, False, None),
               (4, "int64_value", _I64, False, None),
               (5, "str_value", _STR, False, None),
               (7, "ref_value", _U64, False, None)]),
    ("XEventMetadata", [(1, "id", _I64, False, None),
                        (2, "name", _STR, False, None),
                        (5, "stats", _MSG, True, "XStat")]),
    ("XStatMetadata", [(1, "id", _I64, False, None),
                       (2, "name", _STR, False, None)]),
]


@functools.cache
def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    fd = descriptor_pb2.FieldDescriptorProto
    types = {_I64: fd.TYPE_INT64, _U64: fd.TYPE_UINT64,
             _STR: fd.TYPE_STRING, _F64: fd.TYPE_DOUBLE,
             _MSG: fd.TYPE_MESSAGE}
    proto = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for name, fields in _SCHEMA:
        msg = proto.message_type.add(name=name)
        for number, field, kind, repeated, ref in fields:
            f = msg.field.add(name=field, number=number, type=types[kind],
                              label=(fd.LABEL_REPEATED if repeated
                                     else fd.LABEL_OPTIONAL))
            if ref:
                f.type_name = f".bench_xplane.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


@dataclasses.dataclass
class Profile:
    ops: dict      # device plane -> [(tf_op or "", start_ns, end_ns)]
    spans: list    # [(name, start_ns, end_ns)], bench.* and repro.*
    start_ns: int  # profile_start_time, ns since the epoch


def _stat_str(stat, names: dict):
    if stat.ref_value:
        return names.get(stat.ref_value)
    return stat.str_value


def read(path: str) -> Profile:
    """The device ops with their ``tf_op`` and the host spans of the
    profile at ``path``; times in ns as the profile stores them, an
    offset from ``start_ns``, as ``jax.profiler.ProfileData`` gives
    them."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    start = None
    for plane in space.planes:
        if plane.name == "Task Environment":
            names = {e.key: e.value.name for e in plane.stat_metadata}
            for s in plane.stats:
                if names.get(s.metadata_id) == "profile_start_time":
                    start = s.int64_value or s.uint64_value
    if start is None:
        raise ValueError(f"{path}: no profile_start_time")
    ops: dict = {}
    spans: list = []
    for plane in space.planes:
        device = plane.name.startswith(trace.DEVICE_PREFIX)
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        tf_op = {}
        if device:
            for key, m in meta.items():
                tf_op[key] = next((_stat_str(s, names) or "" for s in m.stats
                                   if names.get(s.metadata_id) == "tf_op"),
                                  "")
        for line in plane.lines:
            if device and line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                t0 = line.timestamp_ns + ev.offset_ps * 1e-3
                t1 = t0 + ev.duration_ps * 1e-3
                if device:
                    ops.setdefault(plane.name, []).append(
                        (tf_op[ev.metadata_id], t0, t1))
                else:
                    name = meta[ev.metadata_id].name
                    if name.startswith(SPAN_PREFIXES):
                        spans.append((name, t0, t1))
    return Profile(ops, spans, start)


def scope_of(tf_op: str) -> str:
    """The innermost program scope in an op's ``tf_op`` path."""
    for part in reversed(tf_op.split(":")[0].split("/")):
        if PROGRAM_SCOPE.match(part):
            return part
    return UNSCOPED


def _window(profile: Profile):
    windows = [s for s in profile.spans if s[0] == trace.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace.WINDOW} span, found "
                         f"{len(windows)}")
    return windows[0][1], windows[0][2]


def scope_seconds(profile: Profile) -> dict:
    """Seconds of device time in the window per program scope."""
    lo, hi = _window(profile)
    out: dict = {}
    for events in profile.ops.values():
        for tf_op, start, end in trace.leaves(events):
            d = min(end, hi) - max(start, lo)
            if d > 0:
                key = scope_of(tf_op)
                out[key] = out.get(key, 0.0) + d
    k = len(profile.ops)
    return {key: v / k * 1e-9 for key, v in
            sorted(out.items(), key=lambda kv: -kv[1])}


def idle_by_span(profile: Profile) -> dict:
    """Seconds of the window in which no device op ran, by the
    innermost ``bench.*`` or ``repro.*`` span around each gap."""
    lo, hi = _window(profile)
    inner = [s for s in profile.spans if s[0] != trace.WINDOW]
    out: dict = {}
    for events in profile.ops.values():
        _, gaps = trace.merge([(s, e) for _, s, e in events], lo, hi)
        for gap in gaps:
            label = trace._label(gap, inner)
            out[label] = out.get(label, 0.0) + gap[1] - gap[0]
    k = len(profile.ops)
    return {key: v / k * 1e-9 for key, v in
            sorted(out.items(), key=lambda kv: -kv[1])}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--passes", type=int, default=0,
                    help="passes in the window: adds ms per pass")
    args = ap.parse_args(argv)
    profile = read(args.xplane)
    scopes = scope_seconds(profile)
    out = {"scope_s": scopes, "idle_s": idle_by_span(profile)}
    if args.passes:
        out["scope_ms_per_pass"] = {k: 1e3 * v / args.passes
                                    for k, v in scopes.items()}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
