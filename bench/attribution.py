"""Attribute a traced window to the program's own host spans and to the
graph nodes its device work belongs to.

``bench/trace.py`` reduces a trace to busy time, operation kinds and idle
gaps named after the serve loop's ``bench.*`` spans.  The program also
names its work itself:

* the frontend stamps host spans on its own clock, ``time.perf_counter``
  like the loop's (``Telemetry.spans()``): ``frontend.close`` per
  request, ``frontend.pack``/``put``/``launch``/``wait``/``fetch``/
  ``scatter`` per batch, each with its batch id and request ids;
* each bucket program is the module ``jit_serve_b<bucket>``, and every
  operation of a graph node carries the node's name as a component of
  its ``op_name`` (``jax.named_scope`` in ``GraphPlan.run``).

This module reads those.  ``label_gaps`` names each idle gap after the
most specific host span over it, ``node_times`` charges each device
operation's own time to its graph node as kernel or glue, and
``launch_ties`` checks the clock tie: no program may start on the device
before the host launched it, beyond the tie's own error.  On a program
without per-node scopes, ``node_times`` charges everything to
``UNSCOPED``.
"""
from __future__ import annotations

import collections
import dataclasses
import re
import statistics
from typing import (Container, Dict, Iterable, List, Mapping, Optional,
                    Sequence, Tuple)

from bench import trace
from bench.trace import DEVICE_PLANE, MODULE_LINE, OP_LINE, Event

UNSCOPED = "unscoped"


@dataclasses.dataclass(frozen=True)
class Op(Event):
    """A device event with its string stats and its metadata's."""
    stats: Tuple[Tuple[str, str], ...] = ()

    def stat(self, key: str) -> Optional[str]:
        for k, v in self.stats:
            if k == key:
                return v
        return None


def _xspace_class():
    """A message class for the parts of the profiler's ``XSpace`` proto
    (``tsl/profiler/protobuf/xplane.proto``, field numbers as there)
    that this module reads.  A TPU trace keeps what it knows of an
    operation in the event's metadata, and ``jax.profiler.ProfileData``
    gives an event only its own stats."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    f = descriptor_pb2.FieldDescriptorProto
    fields = {
        "XSpace": [("planes", 1, "XPlane", True)],
        "XPlane": [("name", 2, f.TYPE_STRING, False),
                   ("lines", 3, "XLine", True),
                   ("event_metadata", 4, "XPlane.EventMetadataEntry", True),
                   ("stat_metadata", 5, "XPlane.StatMetadataEntry", True)],
        "XLine": [("name", 2, f.TYPE_STRING, False),
                  ("timestamp_ns", 3, f.TYPE_INT64, False),
                  ("events", 4, "XEvent", True)],
        "XEvent": [("metadata_id", 1, f.TYPE_INT64, False),
                   ("offset_ps", 2, f.TYPE_INT64, False),
                   ("duration_ps", 3, f.TYPE_INT64, False),
                   ("stats", 4, "XStat", True)],
        "XStat": [("metadata_id", 1, f.TYPE_INT64, False),
                  ("str_value", 5, f.TYPE_STRING, False),
                  ("ref_value", 7, f.TYPE_UINT64, False)],
        "XEventMetadata": [("name", 2, f.TYPE_STRING, False),
                           ("stats", 5, "XStat", True)],
        "XStatMetadata": [("name", 2, f.TYPE_STRING, False)],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def add(msg, name, number, kind, repeated):
        fld = msg.field.add(name=name, number=number,
                            label=f.LABEL_REPEATED if repeated
                            else f.LABEL_OPTIONAL)
        if isinstance(kind, str):
            fld.type, fld.type_name = f.TYPE_MESSAGE, f".bench_xplane.{kind}"
        else:
            fld.type = kind
    for name, rows in fields.items():
        msg = fd.message_type.add(name=name)
        for row in rows:
            add(msg, *row)
    plane = next(m for m in fd.message_type if m.name == "XPlane")
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        nested = plane.nested_type.add(name=entry)
        nested.options.map_entry = True
        add(nested, "key", 1, f.TYPE_INT64, False)
        add(nested, "value", 2, value, False)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def load_device(path: str) -> List[Op]:
    """The ``XLA Modules`` and ``XLA Ops`` events of every device plane
    of an ``.xplane.pb``, each with the string stats of the event and of
    its metadata (a reference stat as the name it refers to)."""
    with open(path, "rb") as fh:
        space = _xspace_class().FromString(fh.read())
    out: List[Op] = []
    for pl in space.planes:
        if not DEVICE_PLANE.match(pl.name):
            continue
        names = {k: v.name for k, v in pl.stat_metadata.items()}

        def strings(stats):
            return tuple((names.get(st.metadata_id, ""),
                          names.get(st.ref_value, "") if st.ref_value
                          else st.str_value)
                         for st in stats if st.ref_value or st.str_value)
        meta = {k: (m.name, strings(m.stats))
                for k, m in pl.event_metadata.items()}
        for ln in pl.lines:
            if ln.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in ln.events:
                name, stats = meta.get(ev.metadata_id, ("", ()))
                start = ln.timestamp_ns + ev.offset_ps / 1e3
                out.append(Op(pl.name, ln.name, name, start,
                              start + ev.duration_ps / 1e3,
                              strings(ev.stats) + stats))
    return out


def tied_spans(events: Sequence[Event], spans: Iterable[Sequence],
               anchor_s: float) -> List[Event]:
    """Host spans ``(name, start s, end s, ...)`` — the loop's, or the
    frontend's with their ids — as events on the trace's clock, tied as
    ``trace.host_events`` ties them."""
    return trace.host_events(events, [tuple(s[:3]) for s in spans],
                             anchor_s)


# ---------------------------------------------------------------------------
# idle gaps

def specificity(name: str) -> Optional[int]:
    """How narrowly a host span says what the host did: a batch stage
    (2) over a request's ``frontend.close`` (1) over a loop span (0);
    None for the window and for anything else."""
    if name.startswith("frontend."):
        return 1 if name == "frontend.close" else 2
    if name.startswith("bench.") and name != trace.WINDOW_SPAN:
        return 0
    return None


def _window(events: Sequence[Event]) -> Tuple[float, float]:
    window = [e for e in events if e.name == trace.WINDOW_SPAN
              and not DEVICE_PLANE.match(e.plane)]
    if len(window) != 1:
        raise ValueError(f"expected one {trace.WINDOW_SPAN} host span; "
                         f"found {len(window)}")
    return window[0].start_ns, window[0].end_ns


def _chip(plane: str) -> int:
    return int(plane.rsplit(":", 1)[1])


def idle_gaps(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """The stretches of the window in which the first chip ran no
    operation, in time order (as ``trace.summarize`` finds them, which
    gives only their lengths)."""
    t0, t1 = _window(events)
    per_chip: Dict[str, List] = collections.defaultdict(list)
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.line == OP_LINE:
            a, b = max(e.start_ns, t0), min(e.end_ns, t1)
            if b > a:
                per_chip[e.plane].append((a, b))
    if not per_chip:
        raise ValueError("no device operation ran inside the window")
    first = min(per_chip, key=_chip)
    gaps, edge = [], t0
    for a, b in trace.union(per_chip[first]) + [(t1, t1)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    return gaps


def _cover(intervals: Sequence[Tuple[float, float]], a: float, b: float
           ) -> float:
    clipped = [(max(x, a), min(y, b)) for x, y in intervals
               if min(y, b) > max(x, a)]
    return sum(y - x for x, y in trace.union(clipped))


def label_gaps(events: Sequence[Event], top: int = 10
               ) -> List[Tuple[str, float, Dict[str, float]]]:
    """The ``top`` longest idle gaps, longest first, each as ``(label,
    seconds, cover)``.  ``cover`` is the share of the gap each host span
    name covers (the union of its intervals).  The label is the most
    specific kind of span (``specificity``) whose spans together cover
    at least half of the gap, and of that kind the name that covers
    most; where no kind covers half, the name that covers most; where
    nothing covers the gap, ``other``.  On the loop's spans alone that
    is the label ``trace.summarize`` gives."""
    spans: Dict[str, List] = collections.defaultdict(list)
    for e in events:
        if not DEVICE_PLANE.match(e.plane) and specificity(e.name) is not None:
            spans[e.name].append((e.start_ns, e.end_ns))
    out = []
    for a, b in sorted(idle_gaps(events), key=lambda g: g[0] - g[1])[:top]:
        cover = {n: c / (b - a) for n, iv in spans.items()
                 if (c := _cover(iv, a, b)) > 0}
        label = max(cover, key=cover.get) if cover else "other"
        for level in sorted({specificity(n) for n in cover}, reverse=True):
            names = [n for n in cover if specificity(n) == level]
            if _cover([iv for n in names for iv in spans[n]], a, b) \
                    >= (b - a) / 2:
                label = max(names, key=cover.get)
                break
        out.append((label, (b - a) / 1e9, cover))
    return out


# ---------------------------------------------------------------------------
# device time per graph node

def node_in(name: Optional[str], nodes: Container[str]) -> Optional[str]:
    """The first component of an ``op_name`` path that names a node."""
    for part in (name or "").split("/"):
        if part in nodes:
            return part
    return None


def mac_kind(name: Optional[str]) -> Optional[str]:
    """What an operation's ``op_name`` says of its multiply-adds:
    ``"pallas"`` for a Pallas kernel (``.../pallas_call``), ``"xla"`` for
    XLA's own convolution or dot (an output fusion keeps its name), else
    None."""
    last = (name or "").rsplit("/", 1)[-1]
    if last == "pallas_call":
        return "pallas"
    if last.endswith(("conv_general_dilated", "dot_general")):
        return "xla"
    return None


def op_name(op: Op) -> Optional[str]:
    """The ``op_name`` a TPU trace gives an operation: its metadata's
    ``tf_op`` stat, ``<op_name>:<type>`` with the type empty."""
    v = op.stat("tf_op")
    return v.rsplit(":", 1)[0] if v else None


def node_times(ops: Sequence[Op], t0: float, t1: float,
               nodes: Container[str]) -> Dict[str, Dict[str, float]]:
    """Own device time (s, mean over chips) of the ``XLA Ops`` in
    ``[t0, t1]`` (ns, trace clock) by graph node, ``{node: {"kernel": s,
    "glue": s}}``, ops of no node under ``UNSCOPED`` (all glue).  A
    node's kernel is its Pallas kernel where it has one, else XLA's
    convolution or dot (``mac_kind``); the rest of its ops are glue.
    Own time is as ``trace.self_times`` cuts it, so the total is the
    busy time."""
    dev = [e for e in ops if DEVICE_PLANE.match(e.plane)
           and e.line == OP_LINE]
    keyed = [dataclasses.replace(e, name=str(i)) for i, e in enumerate(dev)]
    by_kind: Dict[str, Dict[Optional[str], float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    for key, a, b in trace.self_times(keyed):
        a, b = max(a, t0), min(b, t1)
        if b > a:
            name = op_name(dev[int(key)])
            node = node_in(name, nodes)
            kind = mac_kind(name) if node else None
            by_kind[node or UNSCOPED][kind] += (b - a) / 1e9
    chips = len({e.plane for e in dev}) or 1
    out = {}
    for node, kinds in by_kind.items():
        mac = "pallas" if kinds.get("pallas") else "xla"
        kernel = kinds.get(mac, 0.0)
        out[node] = {"kernel": kernel / chips,
                     "glue": (sum(kinds.values()) - kernel) / chips}
    return out


def top_nodes(times: Mapping[str, Mapping[str, float]], top: int = 10
              ) -> List[Tuple[str, float, float]]:
    """The ``top`` graph nodes by device time, ``(node, kernel s, glue
    s)``, ``UNSCOPED`` left out."""
    rows = [(n, kg["kernel"], kg["glue"]) for n, kg in times.items()
            if n != UNSCOPED]
    return sorted(rows, key=lambda r: -(r[1] + r[2]))[:top]


# ---------------------------------------------------------------------------
# the clock tie

def launch_ties(events: Sequence[Event]) -> Dict:
    """Per batch, device program start minus ``frontend.launch`` start
    (s, on the tied clock): the i-th launch against the i-th
    ``XLA Modules`` event of the first chip that starts in the window.
    Every program of the window runs on the device inside it, one per
    batch, in launch order.  The first batch is the tie's anchor (its
    program is set to start at its launch), so the minimum and median
    are over the others.  ``before_launch`` counts programs that read
    as starting before their launch; the tie puts them there by at most
    the first batch's own launch-to-start latency."""
    t0, t1 = _window(events)
    launches = sorted(e.start_ns for e in events
                      if e.name == "frontend.launch"
                      and not DEVICE_PLANE.match(e.plane))
    modules = [e for e in events if DEVICE_PLANE.match(e.plane)
               and e.line == MODULE_LINE and t0 <= e.start_ns <= t1]
    first = min((e.plane for e in modules), key=_chip, default=None)
    starts = sorted(e.start_ns for e in modules if e.plane == first)
    d = [(m - la) / 1e9 for la, m in zip(launches, starts)][1:]
    return {"batches": len(launches), "programs": len(starts),
            "min_s": min(d) if d else None,
            "median_s": statistics.median(d) if d else None,
            "before_launch": sum(x < 0 for x in d)}
