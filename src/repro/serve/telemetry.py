"""Per-request serving telemetry: latency stages, host spans and
percentile rollups.

Every request served by the async front end (serve/frontend.py) leaves a
``RequestTrace`` — how long it queued, how long its batches spent in
host→device transfer, how long the device computed, and the wall total —
and every dispatched batch leaves a ``BatchTrace`` (geometry, bucket,
padding, the host timeline of its stages, and whether its transfer
overlapped an in-flight batch — the double-buffering signal).

The timeline is stamped where the work happens, on the frontend's
clock.  Each batch carries a sequence id and the ids of the requests it
carried, and six host spans, one after another (``BATCH_STAGES``):

======================  ================================================
``frontend.pack``       packing the batch's units into one host array
                        (a fed batch: building its slices)
``frontend.put``        ``device_put`` + ``block_until_ready`` of it
``frontend.launch``     the call of the jitted bucket program (async;
                        a fed batch: its assembly, then the program)
``frontend.wait``       ``block_until_ready`` on the result at harvest
``frontend.fetch``      ``device_get`` of the result to host numpy
``frontend.scatter``    scattering the outputs, completing requests
======================  ================================================

A fed batch (serve/frontend.py's feed path) takes its first three on
the feeder thread, so they may overlap the serve thread's spans of
other batches.

and every admitted request one more, ``frontend.close`` (submit → the
close of the batch that took its first unit: the admission policy's
wait).  ``Telemetry.spans()`` lists them as ``(name, start, end,
batch_id, request_ids)`` tuples, the shape a trace reduction ties to a
device trace.

``Telemetry.rollup()`` turns the traces into the machine-readable
summary ``frontend.stats()`` exposes and ``BENCH_graph_serve.json``
records: p50/p95/p99 per request stage (``latency_ms``) and per batch
span (``batch_ms``), deadline-miss counts, overlap counters.

The module is deliberately model-free: it never imports jax and knows
nothing about programs or plans, so any serving layer can record into
it.  All times are seconds from one injected monotonic clock; rollups
convert to milliseconds.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

#: the latency stages every request is accounted under (ms in rollups)
STAGES = ("queue", "transfer", "compute", "total")

#: the host spans of every batch, in the order they happen, each with
#: the ``BatchTrace`` stamps that bound it
BATCH_STAGES = {
    "pack": ("pack_t0", "transfer_t0"),
    "put": ("transfer_t0", "transfer_t1"),
    "launch": ("transfer_t1", "dispatch_t"),
    "wait": ("wait_t0", "wait_t1"),
    "fetch": ("wait_t1", "harvest_t"),
    "scatter": ("harvest_t", "scatter_t1"),
}

#: ``(name, start s, end s, batch_id, request_ids)``
Span = Tuple[str, float, float, int, Tuple[int, ...]]


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default method), monotone
    in ``q`` by construction — so p99 >= p95 >= p50 always holds."""
    if not xs:
        raise ValueError("percentile of empty sequence")
    s = sorted(float(x) for x in xs)
    if len(s) == 1:
        return s[0]
    pos = (q / 100.0) * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    frac = pos - lo
    # lo + (hi - lo) * frac, capped at hi, is monotone under rounding;
    # lo * (1 - frac) + hi * frac is not (a tied tail could read
    # p95 > p99 by one ulp)
    return min(s[lo] + (s[hi] - s[lo]) * frac, s[hi])


def union_length(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals, overlaps
    counted once."""
    total, edge = 0.0, float("-inf")
    for a, b in sorted(intervals):
        a = max(a, edge)
        if b > a:
            total += b - a
            edge = b
    return total


def rollup_percentiles(xs: Sequence[float],
                       qs: Sequence[float] = (50, 95, 99)) -> Dict[str, float]:
    """``{"p50": ..., "p95": ..., "p99": ...}`` for one latency series."""
    return {f"p{int(q)}": percentile(xs, q) for q in qs}


@dataclasses.dataclass
class RequestTrace:
    """One served (or rejected) request's latency accounting.

    ``transfer_ms`` sums over every batch that carried one of the
    request's images (transfers run one after another on the host).
    ``compute_ms`` is the union of those batches' in-flight windows
    (dispatch → observed completion): with double buffering a window
    may include time queued behind the previous batch on the device,
    which is exactly what the request experienced, and two windows of
    one request may overlap, which is counted once.

    ``submit_t``/``close_t`` (frontend clock) bound the request's
    ``frontend.close`` span; ``batch_ids`` lists every batch that
    carried one of its units, in dispatch order.  A request rejected
    at admission has no ``close_t`` and no batches.
    """
    rid: int
    geometry: str                       # "HxWxC"
    images: int
    status: str                         # "served" | "deadline_exceeded"
    deadline_ms: Optional[float]
    queue_ms: float
    transfer_ms: float
    compute_ms: float
    total_ms: float
    submit_t: float = 0.0
    close_t: Optional[float] = None
    batch_ids: Tuple[int, ...] = ()

    @property
    def close_ms(self) -> Optional[float]:
        """Submit → the close of its first batch (None if rejected)."""
        if self.close_t is None:
            return None
        return (self.close_t - self.submit_t) * 1e3

    def stage_ms(self, stage: str) -> float:
        return getattr(self, f"{stage}_ms")


@dataclasses.dataclass
class BatchTrace:
    """One dispatched batch's timeline (all times: seconds on the
    frontend's clock).  ``overlapped`` is True when this batch's
    host→device transfer started while a previous batch was still in
    flight on the device — the double-buffering overlap signal the CI
    smoke test asserts on.  ``shard_units`` (sharded serving only) is
    how many REAL images landed on each mesh device — batch padding
    concentrates in the trailing shards, so ``max - min`` per batch is
    the shard-imbalance signal ``rollup()`` counts.  ``dtype`` is the
    serving dtype of the bucket program that ran the batch (e.g.
    ``"float32"``, ``"bfloat16"``, ``"float32+int8"`` for a quantized
    graph with fp fallback nodes) — stamped by the dispatcher, opaque
    here.

    ``batch_id`` is the batch's dispatch sequence number and
    ``request_ids`` the ids of the requests whose units it carried.
    The stamps bound the ``BATCH_STAGES`` spans back to back:
    ``pack_t0`` → ``transfer_t0`` → ``transfer_t1`` → ``dispatch_t``
    on dispatch (on the feeder thread for a fed batch), ``wait_t0`` →
    ``wait_t1`` → ``harvest_t`` → ``scatter_t1`` on harvest; a stamp
    not taken is None.

    ``slices`` is how many arrays the batch's input was put as (one
    packed array, or the feed path's request-aligned slices) and
    ``view_slices`` how many of those were requests' own arrays, put
    without a host copy."""
    geometry: str
    bucket: int
    units: int                          # real (non-padded) images
    padded: int
    transfer_t0: float
    transfer_t1: float
    dispatch_t: float
    harvest_t: float = 0.0
    overlapped: bool = False
    shard_units: Optional[Sequence[int]] = None    # per-device real images
    dtype: Optional[str] = None         # bucket program's serving dtype
    batch_id: int = -1
    request_ids: Tuple[int, ...] = ()
    pack_t0: Optional[float] = None
    wait_t0: Optional[float] = None
    wait_t1: Optional[float] = None
    scatter_t1: Optional[float] = None
    slices: int = 1
    view_slices: int = 0

    @property
    def transfer_ms(self) -> float:
        return (self.transfer_t1 - self.transfer_t0) * 1e3

    def stage_bounds(self, stage: str
                     ) -> Optional[Tuple[float, float]]:
        """``(start, end)`` of one ``BATCH_STAGES`` span, or None if a
        stamp is missing."""
        a, b = (getattr(self, k) for k in BATCH_STAGES[stage])
        return None if a is None or b is None else (a, b)

    def stage_ms(self, stage: str) -> Optional[float]:
        bounds = self.stage_bounds(stage)
        return None if bounds is None else (bounds[1] - bounds[0]) * 1e3


class Telemetry:
    """Accumulates request/batch traces and rolls them up."""

    def __init__(self):
        self.requests: List[RequestTrace] = []
        self.batches: List[BatchTrace] = []
        self.deadline_misses = 0

    def record_request(self, trace: RequestTrace) -> None:
        self.requests.append(trace)
        if trace.status == "deadline_exceeded":
            self.deadline_misses += 1

    def record_batch(self, trace: BatchTrace) -> None:
        self.batches.append(trace)

    # ------------------------------------------------------------------
    def latency_ms(self) -> Dict[str, Dict[str, float]]:
        """p50/p95/p99 per stage over the *served* requests."""
        served = [t for t in self.requests if t.status == "served"]
        if not served:
            return {}
        return {stage: rollup_percentiles([t.stage_ms(stage)
                                           for t in served])
                for stage in STAGES}

    def batch_ms(self) -> Dict[str, Dict[str, float]]:
        """p50/p95/p99 per batch span (``BATCH_STAGES``) over the
        batches that carry all of that span's stamps."""
        out = {}
        for stage in BATCH_STAGES:
            ms = [m for m in (b.stage_ms(stage) for b in self.batches)
                  if m is not None]
            if ms:
                out[stage] = rollup_percentiles(ms)
        return out

    def spans(self, batches: Optional[Sequence[BatchTrace]] = None,
              requests: Optional[Sequence[RequestTrace]] = None
              ) -> List[Span]:
        """The frontend's host spans, ``(name, start, end, batch_id,
        request_ids)``, of ``batches`` and ``requests`` (default: all
        recorded): one ``frontend.close`` per admitted request, tagged
        with its first batch, and one ``frontend.<stage>`` per stamped
        ``BATCH_STAGES`` span of each batch."""
        out: List[Span] = []
        for r in self.requests if requests is None else requests:
            if r.close_t is not None:
                out.append(("frontend.close", r.submit_t, r.close_t,
                            r.batch_ids[0] if r.batch_ids else -1,
                            (r.rid,)))
        for b in self.batches if batches is None else batches:
            for stage in BATCH_STAGES:
                bounds = b.stage_bounds(stage)
                if bounds is not None:
                    out.append((f"frontend.{stage}", *bounds, b.batch_id,
                                tuple(b.request_ids)))
        return out

    def shard_rollup(self) -> Optional[Dict]:
        """Per-device utilization + imbalance over the sharded batches.

        ``per_device_units`` counts real images landed per mesh device;
        ``per_device_utilization`` divides by that device's offered
        slots (its share of every dispatched bucket).  A batch is
        ``imbalanced`` when its real units don't divide evenly across
        the shards (padding rode the trailing devices); the max
        per-batch spread is reported so a pathological router shows up
        as a number, not a feeling.  None when nothing sharded ran.
        """
        sb = [b for b in self.batches if b.shard_units is not None]
        if not sb:
            return None
        n = max(len(b.shard_units) for b in sb)
        units = [0] * n
        slots = [0] * n
        for b in sb:
            per = b.bucket // len(b.shard_units)
            for i, u in enumerate(b.shard_units):
                units[i] += int(u)
                slots[i] += per
        spreads = [max(b.shard_units) - min(b.shard_units) for b in sb]
        return {
            "devices": n,
            "per_device_units": units,
            "per_device_utilization": [
                u / s if s else 0.0 for u, s in zip(units, slots)],
            "sharded_batches": len(sb),
            "imbalanced_batches": sum(1 for s in spreads if s > 0),
            "max_shard_imbalance": max(spreads),
        }

    def rollup(self) -> Dict:
        """The JSON-ready summary ``frontend.stats()`` builds on."""
        served = [t for t in self.requests if t.status == "served"]
        out = {
            "requests": len(self.requests),
            "served": len(served),
            "deadline_misses": self.deadline_misses,
            "images": sum(t.images for t in served),
            "batches": len(self.batches),
            "padded_slots": sum(b.padded for b in self.batches),
            "overlapped_batches": sum(1 for b in self.batches
                                      if b.overlapped),
            # arrays put, and those put as requests' own arrays
            "slices": sum(b.slices for b in self.batches),
            "view_slices": sum(b.view_slices for b in self.batches),
            "latency_ms": self.latency_ms(),
            "batch_ms": self.batch_ms(),
        }
        dtypes = self.dtype_rollup()
        if dtypes:
            out["serve_dtypes"] = dtypes
        shard = self.shard_rollup()
        if shard is not None:
            out["sharding"] = shard
        return out

    def dtype_rollup(self) -> Dict[str, Dict[str, int]]:
        """Per serving-dtype batch/image counters over the dispatched
        batches — ``{"int8": {"batches": 3, "images": 12}, ...}``.
        Empty when no dispatcher stamped a dtype (older layers)."""
        out: Dict[str, Dict[str, int]] = {}
        for b in self.batches:
            if b.dtype is None:
                continue
            d = out.setdefault(b.dtype, {"batches": 0, "images": 0})
            d["batches"] += 1
            d["images"] += int(b.units)
        return out
