"""Async serving front end: continuous batching, deadline-aware
admission, double-buffered dispatch, multi-resolution routing.

``CnnServeEngine.run()`` is a synchronous drain: every request waits for
the whole queue, host→device transfer serializes with compute, and one
engine serves exactly one image geometry.  ``AsyncServeFrontend`` keeps
the same compiled surface — jitted whole-network bucket programs built
by the shared ``BucketPrograms`` component (serve/cnn.py) — but puts a
scheduler in front of them:

* **Continuous batching.**  Batches close on a *bucket-full or
  ``max_wait_ms``* policy instead of a full drain: a full largest
  bucket dispatches immediately; a short tail dispatches (zero-padded)
  once its oldest request has waited ``max_wait_ms``.  ``poll()`` is the
  streaming entry point (dispatch what the policy allows, never force);
  ``run()`` drains.

* **Deadline-aware admission.**  Requests carry an optional
  ``deadline_ms`` (relative to submit; ``default_deadline_ms`` supplies
  the SLO for requests that don't say).  Within a geometry, admission
  is earliest-deadline-first; a request whose deadline has already
  passed at admission time is rejected with a typed
  ``DeadlineExceeded`` result (``status="deadline_exceeded"``, the
  error naming its lateness) instead of silently served.  A request
  with units already in flight is committed and always completes.

* **Double-buffered dispatch.**  Dispatch is asynchronous: the batch is
  packed on host, ``jax.device_put`` moves it, the program is launched
  without blocking, and the result is harvested (``block_until_ready``)
  only when the pipeline is ``pipeline_depth`` deep or at drain end.
  In steady state batch N+1's host packing + transfer overlaps batch
  N's in-flight compute — every such batch is flagged ``overlapped`` in
  telemetry, the signal the CI smoke test asserts on.

* **The feed path.**  A batch whose packed input is larger than
  ``FEED_BYTES`` is not packed, and the serve thread does not wait for
  its transfer: one feeder thread puts it as request-aligned slices
  (``BucketPrograms.slices``: a slice that is one block of one request
  is that request's own array), assembles them on the device and
  launches the program, batch after batch in dispatch order; harvest
  waits for the feeder's future, then for the result.  Smaller batches,
  and every batch of a sharded frontend, take the inline path above.
  A request's ``images`` may be read in place until it is done.

* **SLO-aware bucket choice.**  When the tightest pending deadline has
  less slack than the close policy's remaining wait (plus
  ``slo_close_margin_ms`` headroom), the batch closes immediately into
  the best-fitting — possibly padded, smaller — bucket instead of
  waiting for a larger one to fill (``stats()["slo_closes"]``).

* **Multi-resolution serving.**  One frontend owns several
  ``(image_shape, buckets)`` programs and routes each request to its
  geometry's bucket set — the one-shape-per-engine restriction is gone.

* **Sharded programs.**  ``mesh=`` (see serve/distributed.py) shards
  every bucket program's batch axis over a 1-D device mesh: configured
  buckets become per-shard capacities, params replicate once, and
  per-batch ``shard_units`` telemetry feeds the per-device
  utilization/imbalance rollups.

* **Telemetry.**  Every request leaves queue/transfer/compute/total
  latency and its ``frontend.close`` span; every batch leaves its
  sequence id, the ids of the requests it carried, and its host spans
  ``frontend.pack``/``put``/``launch`` (stamped in ``_dispatch``) and
  ``frontend.wait``/``fetch``/``scatter`` (stamped in
  ``_harvest_one``), all on the frontend's clock
  (serve/telemetry.py).  The stamps are always taken — about eight
  clock reads per batch.  ``stats()`` exposes p50/p95/p99 rollups per
  request stage (``latency_ms``) and per batch span (``batch_ms``),
  deadline misses, and overlap counters; ``telemetry.spans()`` lists
  the spans for a trace reduction (``bench/attribution.py``).

The scheduler is single-threaded and clock-injected (``clock=``): JAX's
async dispatch provides the device-side concurrency, so behaviour is
deterministic and testable with a fake clock.  The feeder thread only
moves and launches batches the scheduler already formed.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import math
import time
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.cnn import BucketPrograms, ImageRequest, scatter_outputs
from repro.serve.telemetry import (
    BatchTrace, RequestTrace, Telemetry, union_length)

#: a batch whose packed input is larger than this many bytes takes the
#: feed path.  On a TPU v5e (bench/tools/put_timing.py) a packed put of
#: 224x224x3 float32 images takes 2.2 ms at 8 images (4.8 MB), 3.9 ms
#: at 16 and 7.1 ms at 32, where a list of four 8-image views takes
#: 3.5 ms.  A thread that puts while the serve thread polls in a loop
#: waits a switch interval (5 ms) for the GIL and its put takes 23 ms,
#: so the batches of a polling, latency-bound loop (8 images or fewer)
#: keep the serve thread's own put.
FEED_BYTES = 8 << 20

#: request lifecycle states
PENDING = "pending"
SERVED = "served"
DEADLINE_EXCEEDED = "deadline_exceeded"


@dataclasses.dataclass
class DeadlineExceeded:
    """Typed rejection result: the request missed its deadline before
    admission.  ``lateness_ms`` is how far past the deadline admission
    found it."""
    rid: int
    deadline_ms: float
    lateness_ms: float

    def __str__(self):
        return (f"request {self.rid} deadline exceeded: "
                f"{self.lateness_ms:.1f}ms past its "
                f"{self.deadline_ms:.1f}ms deadline")


@dataclasses.dataclass
class ServeRequest(ImageRequest):
    """An ``ImageRequest`` with an optional latency SLO.

    ``deadline_ms`` is relative to submit time; ``None`` defers to the
    frontend's ``default_deadline_ms`` (and if that is also None the
    request never expires).  After serving, ``status`` is ``"served"``
    (outputs in ``out``) or ``"deadline_exceeded"`` (``error`` carries
    the typed ``DeadlineExceeded``; ``out`` stays None).
    """
    deadline_ms: Optional[float] = None
    status: str = PENDING
    error: Optional[DeadlineExceeded] = None
    # -- frontend-internal accounting (stamped at submit/dispatch) -----
    _submit_t: float = 0.0
    _deadline_t: Optional[float] = None     # absolute, frontend clock
    _seq: int = -1
    _close_t: Optional[float] = None        # its first batch closed
    _first_dispatch_t: Optional[float] = None
    _transfer_ms: float = 0.0
    # (dispatch, harvest) of every batch that carried one of its units
    _windows: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)
    _batch_ids: List[int] = dataclasses.field(default_factory=list)
    _served_units: int = 0


@dataclasses.dataclass
class _InFlight:
    """One dispatched, not-yet-harvested batch."""
    shape: Tuple[int, int, int]
    chunk: List[Tuple[ServeRequest, int]]
    result: object      # the async device array, or the feeder's future
    trace: BatchTrace


def _geom(shape: Sequence[int]) -> str:
    return "x".join(str(int(s)) for s in shape)


class AsyncServeFrontend:
    """Continuous-batching front end over shared bucket programs.

    ``geometries`` maps each served ``(H, W, C)`` image shape to its
    bucket tuple, e.g. ``{(32, 32, 3): (1, 4), (16, 16, 3): (1, 2)}`` —
    one frontend, several resolutions, each with its own
    ``BucketPrograms``.  Planning/precision/fusion knobs match
    ``CnnServeEngine`` and apply to every geometry.
    """

    def __init__(self, model, params,
                 geometries: Mapping[Tuple[int, int, int],
                                     Tuple[int, ...]], *,
                 max_wait_ms: float = 2.0,
                 default_deadline_ms: Optional[float] = None,
                 slo_close_margin_ms: float = 0.0,
                 pipeline_depth: int = 2, algorithm="auto",
                 backend: Optional[str] = None, precision=None,
                 fuse: bool = True, input_dtype=None, mesh=None,
                 clock: Callable[[], float] = time.perf_counter):
        if not geometries:
            raise ValueError("geometries must map at least one "
                             "(H, W, C) shape to a bucket tuple")
        if pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1; "
                             f"got {pipeline_depth}")
        # mesh= shards every geometry's bucket programs data-parallel
        # over a 1-D serve mesh: configured buckets become per-shard
        # capacities, params replicate once (see BucketPrograms /
        # serve/distributed.py)
        self.programs: Dict[Tuple[int, int, int], BucketPrograms] = {}
        for shape, buckets in dict(geometries).items():
            shape = tuple(map(int, shape))
            self.programs[shape] = BucketPrograms(
                model, params, shape, buckets=buckets,
                algorithm=algorithm, backend=backend, precision=precision,
                fuse=fuse, input_dtype=input_dtype, mesh=mesh)
        self.model, self.params = model, params
        self.mesh = mesh
        self.max_wait_ms = float(max_wait_ms)
        self.default_deadline_ms = default_deadline_ms
        self.slo_close_margin_ms = float(slo_close_margin_ms)
        self.pipeline_depth = int(pipeline_depth)
        self.telemetry = Telemetry()
        self._clock = clock
        self._pending: Dict[Tuple[int, int, int],
                            List[Tuple[ServeRequest, int]]] = {
            shape: [] for shape in self.programs}
        self._inflight: collections.deque = collections.deque()
        self._completed: List[ServeRequest] = []
        self._seq = 0
        self._batch_seq = 0
        self._max_inflight = 0
        self._slo_closes = 0
        self._batch_counts: Dict[str, int] = {}
        self._feeder: Optional[concurrent.futures.ThreadPoolExecutor] = None

    # ------------------------------------------------------------------
    @property
    def geometries(self) -> Tuple[Tuple[int, int, int], ...]:
        return tuple(self.programs)

    def warmup(self, *, measure: bool = False, tune: Optional[str] = None
               ) -> Dict[str, Dict[int, float]]:
        """Compile every geometry's bucket programs (and the feed path's
        assembly where a bucket feeds); per-bucket compile milliseconds
        keyed by geometry string."""
        out = {}
        for shape, progs in self.programs.items():
            out[_geom(shape)] = progs.warmup(measure=measure, tune=tune)
            for b in progs.buckets:
                if self._feeds(progs, b):
                    progs.warm_feed(b)
        return out

    def close(self) -> None:
        """Stop the feeder thread, once the batches handed to it are
        fed."""
        if self._feeder is not None:
            self._feeder.shutdown()
            self._feeder = None

    # -- admission ------------------------------------------------------
    def submit(self, req: ServeRequest) -> None:
        """Route a request to its geometry's pending queue."""
        shape = tuple(req.images.shape[1:])
        if shape not in self.programs:
            raise ValueError(
                f"request {req.rid}: image shape {shape} matches no "
                f"served geometry {[_geom(s) for s in self.programs]}")
        now = self._clock()
        req._submit_t = now
        req._seq, self._seq = self._seq, self._seq + 1
        deadline = (req.deadline_ms if req.deadline_ms is not None
                    else self.default_deadline_ms)
        req._deadline_t = (None if deadline is None
                           else now + float(deadline) / 1e3)
        self._pending[shape].extend(
            (req, i) for i in range(req.images.shape[0]))

    def _reject(self, req: ServeRequest, now: float) -> None:
        deadline_ms = (req._deadline_t - req._submit_t) * 1e3
        lateness_ms = (now - req._deadline_t) * 1e3
        req.status = DEADLINE_EXCEEDED
        req.error = DeadlineExceeded(req.rid, deadline_ms, lateness_ms)
        req.done = True
        queue_ms = (now - req._submit_t) * 1e3
        self.telemetry.record_request(RequestTrace(
            rid=req.rid, geometry=_geom(req.images.shape[1:]),
            images=int(req.images.shape[0]), status=DEADLINE_EXCEEDED,
            deadline_ms=deadline_ms, queue_ms=queue_ms, transfer_ms=0.0,
            compute_ms=0.0, total_ms=queue_ms))
        self._completed.append(req)

    def _purge_expired(self, shape, now: float) -> None:
        """Deadline-aware admission: requests already past their
        deadline are rejected with a typed result.  Requests with units
        in flight are committed and never purged."""
        pend = self._pending[shape]
        expired = {id(r) for r, _ in pend
                   if r._deadline_t is not None and now > r._deadline_t
                   and r._first_dispatch_t is None}
        if not expired:
            return
        self._pending[shape] = [(r, i) for r, i in pend
                                if id(r) not in expired]
        rejected = {id(r): r for r, _ in pend if id(r) in expired}
        for r in rejected.values():
            self._reject(r, now)

    # -- scheduling -----------------------------------------------------
    def _form_batch(self, shape, now: float, *, force: bool
                    ) -> Optional[Tuple[List, int]]:
        """EDF-order the geometry's pending units and close a batch if
        the policy allows: largest bucket full → dispatch now; else
        dispatch the best-fitting bucket once the oldest pending request
        has waited ``max_wait_ms`` (or unconditionally when draining).

        **SLO-aware close**: when the tightest pending deadline has less
        slack than the wait the close policy would still impose (plus
        ``slo_close_margin_ms`` of service headroom), the batch closes
        NOW into the best-fitting — possibly padded, smaller — bucket
        instead of waiting for a larger one to fill.  A lone
        tight-deadline request is served padded rather than expiring in
        the queue it was asked to wait in."""
        self._purge_expired(shape, now)
        pend = self._pending[shape]
        if not pend:
            return None
        pend.sort(key=lambda u: (
            u[0]._deadline_t if u[0]._deadline_t is not None
            else float("inf"), u[0]._seq, u[1]))
        progs = self.programs[shape]
        bmax = progs.buckets[-1]
        if len(pend) < bmax:
            oldest_wait_ms = (now - min(r._submit_t for r, _ in pend)) * 1e3
            if not force and oldest_wait_ms < self.max_wait_ms:
                remaining_wait_ms = self.max_wait_ms - oldest_wait_ms
                slacks = [(r._deadline_t - now) * 1e3 for r, _ in pend
                          if r._deadline_t is not None]
                tight = min(slacks) if slacks else None
                if (tight is None
                        or tight > remaining_wait_ms
                        + self.slo_close_margin_ms):
                    return None
                self._slo_closes += 1
        b = progs.pick_bucket(len(pend))
        chunk, self._pending[shape] = pend[:b], pend[b:]
        for r, _ in chunk:
            if r._close_t is None:
                r._close_t = now
        return chunk, b

    @staticmethod
    def _feeds(progs: BucketPrograms, bucket: int) -> bool:
        """Whether ``bucket``'s batches take the feed path: unsharded,
        and more than ``FEED_BYTES`` packed."""
        return (progs.mesh is None and bucket * progs.input_dtype().itemsize
                * math.prod(progs.image_shape) > FEED_BYTES)

    def _feed(self, progs: BucketPrograms, chunk, bucket: int):
        """The feeder thread's part of a fed batch: slice it, put the
        slices with one ``device_put``, wait for them, assemble and
        launch.  Returns the async result, the batch's dispatch stamps
        (pack, put, put done, launched) and its slice counts."""
        tp = self._clock()
        xs, views = progs.slices(chunk, bucket)
        t0 = self._clock()
        xs = jax.block_until_ready(progs.put(xs))
        t1 = self._clock()
        y = progs.fn(bucket)(progs.params, progs.assemble(xs))
        return y, (tp, t0, t1, self._clock()), len(xs), views

    def _dispatch(self, shape, chunk, bucket: int) -> None:
        progs = self.programs[shape]
        overlapped = bool(self._inflight)
        if self._feeds(progs, bucket):
            if self._feeder is None:
                self._feeder = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="serve-feeder")
            y = self._feeder.submit(self._feed, progs, list(chunk), bucket)
            # the feeder's own stamps replace these at harvest
            tp = t0 = t1 = td = self._clock()
        else:
            tp = self._clock()
            xb = progs.pack(chunk, bucket)
            # transfer: host blocks only on the COPY — any in-flight
            # batch keeps computing on the device meanwhile (the
            # overlap).  The put is explicit (and sharded under a mesh);
            # params ride the program's own once-replicated tree, never
            # re-transferred.
            t0 = self._clock()
            xd = progs.put(xb)
            jax.block_until_ready(xd)
            t1 = self._clock()
            y = progs.fn(bucket)(progs.params, xd)  # async: no block
            td = self._clock()
        batch_id, self._batch_seq = self._batch_seq, self._batch_seq + 1
        rids: List[int] = []
        for r, _ in chunk:      # a request's units are contiguous
            if r._batch_ids and r._batch_ids[-1] == batch_id:
                continue
            r._batch_ids.append(batch_id)
            rids.append(r.rid)
            if r._first_dispatch_t is None:
                r._first_dispatch_t = t0
        trace = BatchTrace(
            geometry=_geom(shape), bucket=bucket, units=len(chunk),
            padded=bucket - len(chunk), transfer_t0=t0, transfer_t1=t1,
            dispatch_t=td, overlapped=overlapped,
            shard_units=progs.shard_units(len(chunk), bucket),
            dtype=progs.serve_dtype(bucket), batch_id=batch_id,
            request_ids=tuple(rids), pack_t0=tp)
        self._inflight.append(_InFlight(shape, list(chunk), y, trace))
        self._max_inflight = max(self._max_inflight, len(self._inflight))
        key = f"{_geom(shape)}/b{bucket}"
        self._batch_counts[key] = self._batch_counts.get(key, 0) + 1

    def _harvest_one(self) -> None:
        fl = self._inflight.popleft()
        tw = self._clock()
        result = fl.result
        if isinstance(result, concurrent.futures.Future):
            # a fed batch: the feeder's exception, if any, raises here
            result, stamps, slices, views = result.result()
            tr = fl.trace
            tr.pack_t0, tr.transfer_t0, tr.transfer_t1, tr.dispatch_t = stamps
            tr.slices, tr.view_slices = slices, views
            tw = max(tw, tr.dispatch_t)
            for r, _ in fl.chunk:
                if r._batch_ids[0] == tr.batch_id:  # its first put
                    r._first_dispatch_t = tr.transfer_t0
        ready = jax.block_until_ready(result)
        tf = self._clock()
        # device_get is an EXPLICIT device->host gather (sharded outputs
        # reassemble across the mesh), keeping a warm serve loop clean
        # under jax.transfer_guard("disallow")
        y = np.asarray(jax.device_get(ready))
        now = self._clock()
        trace = fl.trace
        trace.wait_t0, trace.wait_t1, trace.harvest_t = tw, tf, now
        self.telemetry.record_batch(trace)
        scatter_outputs(fl.chunk, y)
        seen: Dict[int, ServeRequest] = {}
        counts: Dict[int, int] = {}
        for r, _ in fl.chunk:
            seen[id(r)] = r
            counts[id(r)] = counts.get(id(r), 0) + 1
        for rid_, r in seen.items():
            r._transfer_ms += trace.transfer_ms
            r._windows.append((trace.dispatch_t, trace.harvest_t))
            r._served_units += counts[rid_]
            if r._served_units == r.images.shape[0]:
                self._complete(r, now)
        trace.scatter_t1 = self._clock()

    def _complete(self, req: ServeRequest, now: float) -> None:
        req.status = SERVED
        req.done = True
        deadline_ms = (None if req._deadline_t is None else
                       (req._deadline_t - req._submit_t) * 1e3)
        self.telemetry.record_request(RequestTrace(
            rid=req.rid, geometry=_geom(req.images.shape[1:]),
            images=int(req.images.shape[0]), status=SERVED,
            deadline_ms=deadline_ms,
            queue_ms=(req._first_dispatch_t - req._submit_t) * 1e3,
            transfer_ms=req._transfer_ms,
            compute_ms=union_length(req._windows) * 1e3,
            total_ms=(now - req._submit_t) * 1e3, submit_t=req._submit_t,
            close_t=req._close_t, batch_ids=tuple(req._batch_ids)))
        self._completed.append(req)

    # -- serving entry points -------------------------------------------
    def poll(self) -> List[ServeRequest]:
        """One scheduler pass: dispatch every batch the close policy
        allows, harvesting only when the pipeline is full.  Returns the
        requests that COMPLETED during this pass (served or rejected);
        work still in flight completes on a later ``poll``/``flush``."""
        start = len(self._completed)
        for shape in self.programs:
            while True:
                batch = self._form_batch(shape, self._clock(), force=False)
                if batch is None:
                    break
                self._dispatch(shape, *batch)
                while len(self._inflight) >= self.pipeline_depth:
                    self._harvest_one()
        return self._completed[start:]

    def flush(self) -> List[ServeRequest]:
        """Harvest every in-flight batch; returns newly completed."""
        start = len(self._completed)
        while self._inflight:
            self._harvest_one()
        return self._completed[start:]

    def run(self) -> List[ServeRequest]:
        """Drain everything pending (the ``CnnServeEngine.run``-shaped
        entry point): batches close regardless of ``max_wait_ms``, the
        pipeline stays ``pipeline_depth`` deep, and every submitted
        request comes back completed — served or deadline-rejected — in
        completion order."""
        start = len(self._completed)
        while any(self._pending.values()):
            for shape in self.programs:
                while True:
                    batch = self._form_batch(shape, self._clock(),
                                             force=True)
                    if batch is None:
                        break
                    self._dispatch(shape, *batch)
                    while len(self._inflight) >= self.pipeline_depth:
                        self._harvest_one()
        self.flush()
        return self._completed[start:]

    # -- observability ---------------------------------------------------
    def pending_counts(self) -> Dict[str, int]:
        return {_geom(s): len(u) for s, u in self._pending.items() if u}

    def stats(self) -> Dict:
        """JSON-ready serving summary: request/batch counters, deadline
        misses, double-buffer overlap counters, p50/p95/p99 latency
        rollups per request stage (``latency_ms``:
        queue/transfer/compute/total) and per batch span (``batch_ms``:
        pack/put/launch/wait/fetch/scatter)."""
        st = self.telemetry.rollup()
        served = [t for t in self.telemetry.requests
                  if t.status == SERVED]
        st.update({
            "geometries": [_geom(s) for s in self.programs],
            "batches_by_program": dict(sorted(self._batch_counts.items())),
            # serving dtype per BUILT bucket program ("int8" /
            # "float32+int8" under a QuantPolicy) — unbuilt buckets are
            # omitted rather than force-planned here
            "serve_dtype_by_program": {
                f"{_geom(shape)}/b{b}": progs.serve_dtype(b)
                for shape, progs in self.programs.items()
                for b in progs.compiled_buckets},
            "pending": self.pending_counts(),
            "inflight": len(self._inflight),
            "max_inflight": self._max_inflight,
            # batches closed early because a pending deadline was
            # tighter than the remaining close-policy wait
            "slo_closes": self._slo_closes,
            # served past their deadline (admitted on time, finished
            # late) — distinct from admission-rejected deadline_misses
            "late_served": sum(
                1 for t in served
                if t.deadline_ms is not None and t.total_ms > t.deadline_ms),
        })
        return st
