"""Async serving front end (serve/frontend.py): continuous batching,
deadline-aware admission, double-buffered dispatch, multi-resolution
routing, and per-request telemetry."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import convspec as cs
from repro.core import cuconv as cc
from repro.models.cnn import SimpleCNN, resnet_like
from repro.serve.frontend import (
    DEADLINE_EXCEEDED, SERVED, AsyncServeFrontend, DeadlineExceeded,
    ServeRequest)
from repro.serve.telemetry import BATCH_STAGES


TINY = [(3, 3, 6, 2), (1, 1, 4, 1)]


def _lax_model_ref(model, params, x):
    y = x
    for p, (kh, kw, co, s) in zip(params["convs"], model.spec):
        y = jax.nn.relu(cc.conv_lax(y, p["w"], s, "same") + p["b"])
    return y.mean(axis=(1, 2)) @ params["head"]


class FakeClock:
    """Deterministic injectable clock (seconds); advance in ms."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance_ms(self, ms: float) -> None:
        self.t += ms / 1e3


@pytest.fixture
def tiny():
    model = SimpleCNN(TINY, num_classes=3)
    return model, model.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# correctness: multi-resolution serving

def test_multi_resolution_mixed_stream_matches_reference(rng, tiny):
    """One frontend, two image geometries: every submitted image is
    served exactly once through its geometry's bucket set, outputs
    matching the unbatched lax reference."""
    model, params = tiny
    fe = AsyncServeFrontend(model, params,
                            {(16, 16, 3): (1, 4), (8, 8, 3): (1, 2)})
    fe.warmup()
    sizes = [(1, 16), (3, 8), (5, 16), (2, 8), (1, 8), (4, 16)]
    reqs = [ServeRequest(rid=i, images=rng.normal(
        size=(n, hw, hw, 3)).astype(np.float32))
        for i, (n, hw) in enumerate(sizes)]
    for r in reqs:
        fe.submit(r)
    cs.reset_plan_stats()
    done = fe.run()
    assert cs.PLAN_STATS["resolutions"] == 0    # warm frontend: no re-plans
    assert sorted(r.rid for r in done) == list(range(len(sizes)))
    assert all(r.status == SERVED and r.done for r in done)
    st = fe.stats()
    assert st["images"] == sum(n for n, _ in sizes)
    assert set(st["geometries"]) == {"16x16x3", "8x8x3"}
    for r in reqs:
        assert r.out.shape == (r.images.shape[0], 3)
        for i in range(r.images.shape[0]):
            ref = _lax_model_ref(model, params,
                                 jnp.asarray(r.images[i:i + 1]))
            np.testing.assert_allclose(r.out[i], np.asarray(ref)[0],
                                       rtol=3e-4, atol=3e-4,
                                       err_msg=f"req {r.rid} image {i}")


def test_rejects_unserved_geometry(rng, tiny):
    model, params = tiny
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (1,)})
    with pytest.raises(ValueError, match="matches no served geometry"):
        fe.submit(ServeRequest(rid=0, images=rng.normal(
            size=(1, 12, 12, 3)).astype(np.float32)))
    with pytest.raises(ValueError, match="geometries"):
        AsyncServeFrontend(model, params, {})


# ---------------------------------------------------------------------------
# deadline-aware admission

def test_expired_request_rejected_with_typed_result(rng, tiny):
    """A request whose deadline passed before admission comes back
    status=deadline_exceeded with a typed DeadlineExceeded error — not
    silently served — and counts as a deadline miss."""
    model, params = tiny
    clock = FakeClock()
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (2,)}, clock=clock)
    fe.warmup()
    late = ServeRequest(rid=0, images=rng.normal(
        size=(2, 8, 8, 3)).astype(np.float32), deadline_ms=10.0)
    ok = ServeRequest(rid=1, images=rng.normal(
        size=(1, 8, 8, 3)).astype(np.float32), deadline_ms=10_000.0)
    fe.submit(late)
    fe.submit(ok)
    clock.advance_ms(50.0)          # past late's deadline, within ok's
    done = fe.run()
    by_rid = {r.rid: r for r in done}
    assert by_rid[0].status == DEADLINE_EXCEEDED
    assert isinstance(by_rid[0].error, DeadlineExceeded)
    assert by_rid[0].error.rid == 0
    assert by_rid[0].error.deadline_ms == pytest.approx(10.0)
    assert by_rid[0].error.lateness_ms == pytest.approx(40.0)
    assert by_rid[0].out is None and by_rid[0].done
    assert by_rid[1].status == SERVED and by_rid[1].out is not None
    st = fe.stats()
    assert st["deadline_misses"] == 1
    assert st["served"] == 1


def test_default_deadline_applies_to_unmarked_requests(rng, tiny):
    model, params = tiny
    clock = FakeClock()
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (1,)},
                            default_deadline_ms=20.0, clock=clock)
    fe.warmup()
    fe.submit(ServeRequest(rid=0, images=rng.normal(
        size=(1, 8, 8, 3)).astype(np.float32)))       # inherits 20ms SLO
    fe.submit(ServeRequest(rid=1, images=rng.normal(
        size=(1, 8, 8, 3)).astype(np.float32), deadline_ms=500.0))
    clock.advance_ms(100.0)
    done = fe.run()
    by_rid = {r.rid: r for r in done}
    assert by_rid[0].status == DEADLINE_EXCEEDED
    assert by_rid[1].status == SERVED


def test_admission_is_edf_within_a_bucket(rng, tiny):
    """Earlier deadlines dispatch first regardless of submit order."""
    model, params = tiny
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (1,)})
    fe.warmup()
    a = ServeRequest(rid=0, images=rng.normal(
        size=(1, 8, 8, 3)).astype(np.float32), deadline_ms=60_000.0)
    b = ServeRequest(rid=1, images=rng.normal(
        size=(1, 8, 8, 3)).astype(np.float32), deadline_ms=1_000.0)
    c = ServeRequest(rid=2, images=rng.normal(
        size=(1, 8, 8, 3)).astype(np.float32))        # no deadline: last
    for r in (a, c, b):
        fe.submit(r)
    done = fe.run()
    assert [r.rid for r in done] == [1, 0, 2]   # completion order == EDF


def test_committed_request_completes_despite_late_deadline(rng, tiny):
    """A request with units already in flight is never purged — it was
    admitted on time and always completes (late_served accounts it)."""
    model, params = tiny
    clock = FakeClock()
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (2,)},
                            pipeline_depth=2, clock=clock)
    fe.warmup()
    # 3 units: first batch of 2 dispatches, then the deadline passes
    # before the tail unit is admitted
    r = ServeRequest(rid=0, images=rng.normal(
        size=(3, 8, 8, 3)).astype(np.float32), deadline_ms=10.0)
    fe.submit(r)
    fe.poll()                       # bucket-full: dispatches (r, 0..1)
    clock.advance_ms(50.0)          # deadline passes mid-request
    done = fe.run()
    assert [x.rid for x in done] == [0]
    assert done[0].status == SERVED
    assert fe.stats()["deadline_misses"] == 0
    assert fe.stats()["late_served"] == 1


# ---------------------------------------------------------------------------
# continuous batching: the bucket-full-or-max-wait close policy

def test_short_batch_waits_for_max_wait(rng, tiny):
    model, params = tiny
    clock = FakeClock()
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (4,)},
                            max_wait_ms=10.0, clock=clock)
    fe.warmup()
    fe.submit(ServeRequest(rid=0, images=rng.normal(
        size=(1, 8, 8, 3)).astype(np.float32)))
    assert fe.poll() == [] and fe.stats()["batches"] == 0   # still waiting
    clock.advance_ms(5.0)
    assert fe.poll() == [] and fe.stats()["batches"] == 0   # not yet
    clock.advance_ms(6.0)                                   # 11ms > 10ms
    fe.poll()
    done = fe.flush()
    assert [r.rid for r in done] == [0] and done[0].status == SERVED
    st = fe.stats()
    assert st["batches"] == 1
    assert st["padded_slots"] == 3      # 1 unit rode the 4-bucket padded


def test_full_bucket_dispatches_without_waiting(rng, tiny):
    model, params = tiny
    clock = FakeClock()
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (1, 4)},
                            max_wait_ms=10_000.0, clock=clock)
    fe.warmup()
    fe.submit(ServeRequest(rid=0, images=rng.normal(
        size=(4, 8, 8, 3)).astype(np.float32)))
    fe.poll()                       # zero wall-clock has passed
    done = fe.flush()
    assert [r.rid for r in done] == [0]
    assert fe.stats()["batches"] == 1
    assert fe.stats()["padded_slots"] == 0


def test_tight_deadline_closes_batch_before_max_wait(rng, tiny):
    """SLO-aware close: a pending deadline with less slack than the
    remaining close-policy wait dispatches NOW — padded into the
    bucket — instead of expiring in the queue it was told to wait in."""
    model, params = tiny
    clock = FakeClock()
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (4,)},
                            max_wait_ms=10.0, clock=clock)
    fe.warmup()
    fe.submit(ServeRequest(rid=0, images=rng.normal(
        size=(1, 8, 8, 3)).astype(np.float32), deadline_ms=3.0))
    fe.poll()                       # slack 3ms < 10ms remaining wait
    done = fe.flush()
    assert [r.rid for r in done] == [0] and done[0].status == SERVED
    st = fe.stats()
    assert st["slo_closes"] == 1
    assert st["batches"] == 1 and st["padded_slots"] == 3
    assert st["deadline_misses"] == 0 and st["late_served"] == 0


def test_loose_deadline_still_waits_for_max_wait(rng, tiny):
    """A deadline with plenty of slack does NOT trigger the SLO close —
    the short batch keeps its max_wait patience for more traffic."""
    model, params = tiny
    clock = FakeClock()
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (4,)},
                            max_wait_ms=10.0, clock=clock)
    fe.warmup()
    fe.submit(ServeRequest(rid=0, images=rng.normal(
        size=(1, 8, 8, 3)).astype(np.float32), deadline_ms=50.0))
    assert fe.poll() == [] and fe.stats()["batches"] == 0
    clock.advance_ms(4.0)           # slack 46ms > 6ms remaining: wait on
    assert fe.poll() == [] and fe.stats()["batches"] == 0
    clock.advance_ms(7.0)           # 11ms > max_wait: the NORMAL close
    fe.poll()
    done = fe.flush()
    assert [r.rid for r in done] == [0] and done[0].status == SERVED
    assert fe.stats()["slo_closes"] == 0


def test_slo_close_margin_adds_service_headroom(rng, tiny):
    """slo_close_margin_ms widens what counts as 'tight': a 12ms
    deadline against 10ms of remaining wait is loose at margin 0 but
    tight at margin 5 (12 <= 10 + 5)."""
    model, params = tiny
    clock = FakeClock()
    fe0 = AsyncServeFrontend(model, params, {(8, 8, 3): (4,)},
                             max_wait_ms=10.0, clock=clock)
    fe0.submit(ServeRequest(rid=0, images=rng.normal(
        size=(1, 8, 8, 3)).astype(np.float32), deadline_ms=12.0))
    assert fe0.poll() == [] and fe0.stats()["slo_closes"] == 0
    fe5 = AsyncServeFrontend(model, params, {(8, 8, 3): (4,)},
                             max_wait_ms=10.0, slo_close_margin_ms=5.0,
                             clock=clock)
    fe5.warmup()
    fe5.submit(ServeRequest(rid=0, images=rng.normal(
        size=(1, 8, 8, 3)).astype(np.float32), deadline_ms=12.0))
    fe5.poll()
    done = fe5.flush()
    assert [r.rid for r in done] == [0] and done[0].status == SERVED
    assert fe5.stats()["slo_closes"] == 1


# ---------------------------------------------------------------------------
# double-buffered dispatch

def test_steady_state_batches_overlap_transfer_with_compute(rng, tiny):
    """With >= 2 batches the pipeline keeps one batch in flight while
    the next is packed + transferred: every steady-state batch is
    flagged overlapped, and the pipeline never exceeds its depth."""
    model, params = tiny
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (2,)},
                            pipeline_depth=2)
    fe.warmup()
    for i in range(5):
        fe.submit(ServeRequest(rid=i, images=rng.normal(
            size=(2, 8, 8, 3)).astype(np.float32)))
    done = fe.run()
    assert len(done) == 5
    st = fe.stats()
    assert st["batches"] == 5
    # batch 0 has nothing to overlap; every later batch transferred
    # while its predecessor was still in flight
    assert st["overlapped_batches"] == 4
    assert st["max_inflight"] == 2      # depth respected, and reached
    assert st["inflight"] == 0
    for prev, nxt in zip(fe.telemetry.batches, fe.telemetry.batches[1:]):
        assert nxt.overlapped
        assert nxt.transfer_t0 < prev.harvest_t   # the overlap window


def test_pipeline_depth_one_never_overlaps(rng, tiny):
    model, params = tiny
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (2,)},
                            pipeline_depth=1)
    fe.warmup()
    for i in range(3):
        fe.submit(ServeRequest(rid=i, images=rng.normal(
            size=(2, 8, 8, 3)).astype(np.float32)))
    fe.run()
    st = fe.stats()
    assert st["overlapped_batches"] == 0
    assert st["max_inflight"] == 1


# ---------------------------------------------------------------------------
# telemetry

def test_stats_rollups_are_complete_and_json_ready(rng, tiny):
    model, params = tiny
    fe = AsyncServeFrontend(model, params,
                            {(16, 16, 3): (1, 4), (8, 8, 3): (1, 2)})
    fe.warmup()
    for i, (n, hw) in enumerate([(2, 16), (1, 8), (3, 16), (2, 8)]):
        fe.submit(ServeRequest(rid=i, images=rng.normal(
            size=(n, hw, hw, 3)).astype(np.float32),
            deadline_ms=60_000.0))
    fe.run()
    st = fe.stats()
    json.dumps(st)                      # must be JSON-serializable
    lat = st["latency_ms"]
    assert set(lat) == {"queue", "transfer", "compute", "total"}
    for stage, ps in lat.items():
        assert set(ps) == {"p50", "p95", "p99"}
        assert ps["p50"] <= ps["p95"] <= ps["p99"], stage
        assert all(v >= 0.0 for v in ps.values()), stage
    assert st["requests"] == st["served"] == 4
    assert st["deadline_misses"] == 0
    # per-request accounting: total covers queue+compute for every trace
    for t in fe.telemetry.requests:
        assert t.total_ms >= t.compute_ms
        assert t.total_ms >= t.queue_ms


def test_warmup_compiles_exactly_the_trace_that_serves(rng, tiny):
    """Requests arriving in ANY host dtype are packed to the one
    input_dtype() the warmup dummy compiled — serving triggers zero
    retraces on the warm programs."""
    model, params = tiny
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (1, 2)})
    fe.warmup()
    fe.submit(ServeRequest(rid=0, images=rng.normal(
        size=(3, 8, 8, 3))))            # float64 host images
    fe.submit(ServeRequest(rid=1, images=rng.normal(
        size=(2, 8, 8, 3)).astype(np.float16)))
    done = fe.run()
    assert all(r.status == SERVED for r in done)
    for b, fn in fe.programs[(8, 8, 3)]._fns.items():
        assert fn._cache_size() == 1, f"bucket {b} retraced while serving"
    for r in done:
        for i in range(r.images.shape[0]):
            ref = _lax_model_ref(model, params, jnp.asarray(
                r.images[i:i + 1], jnp.float32))
            np.testing.assert_allclose(r.out[i], np.asarray(ref)[0],
                                       rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# acceptance: an IR model at two resolutions through one frontend

def test_acceptance_resnet_two_resolutions_zero_misses(rng):
    from repro.configs.serve import SMOKE_FRONTEND
    model = resnet_like(num_classes=4)
    params = model.init(jax.random.PRNGKey(0))
    fe = AsyncServeFrontend(
        model, params, SMOKE_FRONTEND.geometry_map(),
        max_wait_ms=SMOKE_FRONTEND.max_wait_ms,
        default_deadline_ms=SMOKE_FRONTEND.default_deadline_ms,
        pipeline_depth=SMOKE_FRONTEND.pipeline_depth)
    fe.warmup()
    for i, (n, hw) in enumerate([(1, 32), (2, 16), (4, 32), (1, 16),
                                 (3, 32), (2, 16)]):
        fe.submit(ServeRequest(rid=i, images=rng.normal(
            size=(n, hw, hw, 3)).astype(np.float32),
            deadline_ms=None if i % 2 else 30_000.0))
    done = fe.run()
    assert all(r.status == SERVED for r in done)
    st = fe.stats()
    assert st["deadline_misses"] == 0 and st["late_served"] == 0
    assert st["served"] == 6
    assert len(st["batches_by_program"]) >= 2   # both geometries dispatched
    assert st["latency_ms"]["total"]["p99"] >= st["latency_ms"]["total"]["p50"]


# ---------------------------------------------------------------------------
# host spans: the batch timeline, its ids, and the close span

class SteppingClock:
    """Deterministic clock that moves 1 ms on every reading, so every
    stamp the frontend takes is distinct and ordered."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1e-3
        return self.t


def _stream(rng, fe, sizes, hw=8):
    for i, n in enumerate(sizes):
        fe.submit(ServeRequest(rid=i, images=rng.normal(
            size=(n, hw, hw, 3)).astype(np.float32)))


def test_compute_ms_counts_overlapping_batch_windows_once(rng, tiny):
    """One request split over two batches in flight together: its
    compute time is the union of their windows (first dispatch → last
    harvest), not their sum, and never exceeds its total."""
    model, params = tiny
    clock = SteppingClock()
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (2,)},
                            pipeline_depth=2, clock=clock)
    fe.warmup()
    _stream(rng, fe, [4])
    fe.run()
    a, b = fe.telemetry.batches
    assert b.dispatch_t < a.harvest_t           # the windows overlap
    (t,) = fe.telemetry.requests
    assert t.compute_ms == pytest.approx((b.harvest_t - a.dispatch_t) * 1e3)
    windows = (a.harvest_t - a.dispatch_t) + (b.harvest_t - b.dispatch_t)
    assert t.compute_ms < windows * 1e3         # the sum counted twice
    assert t.total_ms >= t.compute_ms


def test_batch_spans_are_ordered_and_inside_poll_and_flush(rng, tiny):
    model, params = tiny
    clock = SteppingClock()
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (1, 2)},
                            max_wait_ms=0.0, pipeline_depth=2, clock=clock)
    fe.warmup()
    calls = []
    for sizes in ([3, 1], [2], [1, 1, 2]):
        _stream(rng, fe, sizes)
        for entry in (fe.poll, fe.flush):
            t0 = clock()
            entry()
            calls.append((t0, clock()))
    assert len(fe.telemetry.batches) >= 5
    for b in fe.telemetry.batches:
        bounds = [b.stage_bounds(s) for s in BATCH_STAGES]
        edge = float("-inf")
        for a, z in bounds:             # pack ≤ put ≤ … ≤ scatter
            assert edge <= a <= z
            edge = z
        for group in (bounds[:3], bounds[3:]):    # dispatch, harvest
            assert any(c0 <= group[0][0] and group[-1][1] <= c1
                       for c0, c1 in calls)


def test_batch_and_request_ids_link_requests_to_their_batches(rng, tiny):
    model, params = tiny
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (1, 2, 4)})
    fe.warmup()
    sizes = [3, 1, 6, 2, 1]
    _stream(rng, fe, sizes)
    fe.run()
    batches = fe.telemetry.batches
    assert sorted(b.batch_id for b in batches) == list(range(len(batches)))
    by_id = {b.batch_id: b for b in batches}
    for t in fe.telemetry.requests:
        carried = [b.batch_id for b in batches if t.rid in b.request_ids]
        assert list(t.batch_ids) == sorted(carried) and carried
    assert sum(len(by_id[i].request_ids) for i in by_id) >= len(sizes)
    # every unit of a split request rode one of its batches
    assert sum(b.units for b in batches) == sum(sizes)
    spans = fe.telemetry.spans()
    names = {s[0] for s in spans}
    assert names == {"frontend.close"} | {f"frontend.{s}"
                                          for s in BATCH_STAGES}
    for name, a, z, bid, rids in spans:
        assert a <= z
        if name != "frontend.close":
            assert rids == by_id[bid].request_ids


def test_close_span_ends_before_its_first_batch_packs(rng, tiny):
    model, params = tiny
    clock = SteppingClock()
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (2, 4)},
                            max_wait_ms=5.0, clock=clock)
    fe.warmup()
    _stream(rng, fe, [1, 3, 2, 1])
    fe.poll()                           # closes the full 4-bucket only
    fe.run()
    by_id = {b.batch_id: b for b in fe.telemetry.batches}
    closes = {rids[0]: (a, z, bid) for name, a, z, bid, rids
              in fe.telemetry.spans() if name == "frontend.close"}
    assert sorted(closes) == [0, 1, 2, 3]
    for t in fe.telemetry.requests:
        a, z, bid = closes[t.rid]
        assert (a, z, bid) == (t.submit_t, t.close_t, t.batch_ids[0])
        assert t.submit_t <= t.close_t <= by_id[bid].pack_t0
        assert t.close_ms == pytest.approx((t.close_t - t.submit_t) * 1e3)
        assert t.close_ms <= t.queue_ms


def test_batch_ms_rollup_is_json_ready_and_monotone(rng, tiny):
    model, params = tiny
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (1, 2)})
    fe.warmup()
    _stream(rng, fe, [2, 1, 3, 2, 1])
    fe.run()
    st = json.loads(json.dumps(fe.stats()))
    assert list(st["batch_ms"]) == list(BATCH_STAGES)
    for stage, ps in st["batch_ms"].items():
        assert ps["p50"] <= ps["p95"] <= ps["p99"], stage
        assert ps["p50"] >= 0.0, stage


@pytest.mark.parametrize("intervals,length", [
    ([], 0.0),
    ([(0.0, 2.0), (1.0, 3.0)], 3.0),            # overlap counted once
    ([(5.0, 6.0), (0.0, 1.0)], 2.0),            # disjoint, unsorted
    ([(0.0, 4.0), (1.0, 2.0)], 4.0),            # nested
])
def test_union_length(intervals, length):
    from repro.serve.telemetry import union_length
    assert union_length(intervals) == pytest.approx(length)


def test_spans_skip_missing_stamps_and_rejected_requests():
    from repro.serve.telemetry import BatchTrace, RequestTrace, Telemetry
    t = Telemetry()
    t.record_batch(BatchTrace(geometry="8x8x3", bucket=2, units=2, padded=0,
                              transfer_t0=1.0, transfer_t1=2.0,
                              dispatch_t=3.0, batch_id=0,
                              request_ids=(7,)))
    t.record_request(RequestTrace(
        rid=8, geometry="8x8x3", images=1, status=DEADLINE_EXCEEDED,
        deadline_ms=1.0, queue_ms=5.0, transfer_ms=0.0, compute_ms=0.0,
        total_ms=5.0))
    assert t.spans() == [("frontend.put", 1.0, 2.0, 0, (7,)),
                         ("frontend.launch", 2.0, 3.0, 0, (7,))]
    assert list(t.batch_ms()) == ["put", "launch"]
    assert t.requests[0].close_ms is None


# ---------------------------------------------------------------------------
# the feed path: request-aligned slices put by the feeder thread

@pytest.fixture
def feed_all(monkeypatch):
    """Every batch of the tiny geometries takes the feed path."""
    from repro.serve import frontend
    monkeypatch.setattr(frontend, "FEED_BYTES", 0)


def _serve_both(monkeypatch, model, params, reqs, buckets, **kw):
    """Serve ``reqs`` (rid -> images) on the inline path, then on the
    feed path; both frontends and their done lists."""
    from repro.serve import frontend
    out = []
    for feed_bytes in (frontend.FEED_BYTES, 0):
        monkeypatch.setattr(frontend, "FEED_BYTES", feed_bytes)
        fe = AsyncServeFrontend(model, params, {(8, 8, 3): buckets}, **kw)
        fe.warmup()
        for rid, x in reqs.items():
            fe.submit(ServeRequest(rid=rid, images=x))
        done = fe.run()
        fe.close()
        out.append((fe, done))
    return out


@pytest.mark.parametrize("sizes,buckets", [
    ([2, 2, 2, 2, 2, 2], (2, 4)),           # request-aligned full batches
    ([1, 3, 3, 1, 3, 1, 1], (2, 4)),        # slices that mix requests
    ([2, 2, 1], (2, 4)),                    # a partly padded slice
    ([3, 2, 2, 1], (2, 3)),                 # 2 does not divide 3
])
def test_feed_path_serves_bitwise_what_the_inline_path_serves(
        rng, tiny, monkeypatch, sizes, buckets):
    model, params = tiny
    reqs = {i: rng.normal(size=(n, 8, 8, 3)).astype(np.float32)
            for i, n in enumerate(sizes)}
    (inline, done_i), (fed, done_f) = _serve_both(
        monkeypatch, model, params, reqs, buckets)
    assert inline._feeder is None and inline.stats()["view_slices"] == 0
    assert [r.rid for r in done_f] == [r.rid for r in done_i]
    for a, b in zip(done_i, done_f):
        assert a.status == b.status == SERVED
        np.testing.assert_array_equal(a.out, b.out)
    for bi, bf in zip(inline.telemetry.batches, fed.telemetry.batches):
        assert (bi.bucket, bi.units, bi.request_ids) == (
            bf.bucket, bf.units, bf.request_ids)
        s = fed.programs[(8, 8, 3)].slice_size(bf.bucket)
        assert bf.slices == bf.bucket // s
        assert bi.slices == 1 and bi.view_slices == 0
    st = fed.stats()
    assert st["slices"] == sum(b.slices for b in fed.telemetry.batches)
    if sizes == [2] * 6:                # every slice one request's array
        assert st["view_slices"] == st["slices"] == 6
    else:
        assert st["view_slices"] < st["slices"]


def test_slices_are_views_packs_and_the_device_zero_slice(rng, tiny):
    """The slice rule at the component: a one-request block in the
    packing dtype is that request's own memory, a mixed, cast or short
    slice is packed at slice size, an all-padding slice is the one
    device-resident zero slice; assembled, they are the packed batch."""
    from repro.serve.cnn import BucketPrograms, ImageRequest
    model, params = tiny
    progs = BucketPrograms(model, params, (8, 8, 3), buckets=(2, 8))
    a = ImageRequest(0, rng.normal(size=(3, 8, 8, 3)).astype(np.float32))
    b = ImageRequest(1, rng.normal(size=(2, 8, 8, 3)))      # float64
    chunk = [(a, 0), (a, 1), (a, 2), (b, 0), (b, 1)]
    xs, views = progs.slices(chunk, 8)
    assert progs.slice_size(8) == 2 and len(xs) == 4 and views == 1
    assert np.shares_memory(xs[0], a.images)
    assert xs[1].shape == xs[2].shape == (2, 8, 8, 3)
    assert xs[1].dtype == xs[2].dtype == np.float32
    assert xs[3] is progs.zero_slice()
    assert isinstance(xs[3], jax.Array)
    np.testing.assert_array_equal(
        np.asarray(progs.assemble(progs.put(xs))), progs.pack(chunk, 8))
    fn = progs.fn(8)
    np.testing.assert_array_equal(
        np.asarray(fn(params, progs.assemble(progs.put(xs)))),
        np.asarray(fn(params, progs.put(progs.pack(chunk, 8)))))


def test_feed_path_keeps_the_pipeline_depth_and_one_transfer_at_a_time(
        rng, tiny, feed_all):
    """Batches queue at the feeder (each feed is slowed), yet no more
    than ``pipeline_depth`` are ever in flight, the depth is reached,
    transfers never overlap, and requests complete in order."""
    import time
    model, params = tiny
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (2,)},
                            pipeline_depth=2)
    fe.warmup()
    feed = fe._feed
    queued = []

    def slow_feed(*a):
        queued.append(len(fe._inflight))
        time.sleep(0.02)
        return feed(*a)
    fe._feed = slow_feed
    _stream(rng, fe, [2, 2, 2, 2, 2])
    done = fe.run()
    fe.close()
    assert [r.rid for r in done] == list(range(5))
    st = fe.stats()
    assert st["max_inflight"] == 2 and st["inflight"] == 0
    assert max(queued) <= 2
    assert st["overlapped_batches"] == 4
    batches = sorted(fe.telemetry.batches, key=lambda b: b.batch_id)
    for prev, nxt in zip(batches, batches[1:]):
        assert prev.transfer_t1 <= nxt.transfer_t0      # FIFO feeder
    for b in batches:
        bounds = [b.stage_bounds(s) for s in BATCH_STAGES]
        assert all(x[0] <= x[1] <= y[0] for x, y in zip(bounds, bounds[1:]))
    for t in fe.telemetry.requests:
        assert t.queue_ms >= 0.0 and t.total_ms >= t.compute_ms


def test_feeder_exception_surfaces_at_poll_and_flush(rng, tiny, feed_all):
    model, params = tiny
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (2,)},
                            pipeline_depth=2)
    fe.warmup()

    def boom(chunk, bucket):
        raise RuntimeError("feeder boom")
    fe.programs[(8, 8, 3)].slices = boom
    _stream(rng, fe, [2])
    assert fe.poll() == []              # handed to the feeder, not harvested
    with pytest.raises(RuntimeError, match="feeder boom"):
        fe.flush()
    _stream(rng, fe, [2, 2])
    with pytest.raises(RuntimeError, match="feeder boom"):
        fe.poll()                       # the depth forces a harvest
    fe.close()


def test_warmup_compiles_exactly_the_trace_that_serves_on_the_feed_path(
        rng, tiny, feed_all):
    """The inline test's requests in other host dtypes, fed: each is
    cast as its slice is packed, and serving compiles nothing — no
    bucket program retraces, the assembly step neither."""
    from repro.serve import cnn
    model, params = tiny
    fe = AsyncServeFrontend(model, params, {(8, 8, 3): (1, 2)})
    fe.warmup()
    assembled = cnn._assemble._cache_size()
    fe.submit(ServeRequest(rid=0, images=rng.normal(
        size=(3, 8, 8, 3))))            # float64 host images
    fe.submit(ServeRequest(rid=1, images=rng.normal(
        size=(2, 8, 8, 3)).astype(np.float16)))
    done = fe.run()
    fe.close()
    assert all(r.status == SERVED for r in done)
    assert fe.stats()["view_slices"] == 0          # every slice was cast
    assert all(b.slices == b.bucket for b in fe.telemetry.batches)
    for b, fn in fe.programs[(8, 8, 3)]._fns.items():
        assert fn._cache_size() == 1, f"bucket {b} retraced while serving"
    assert cnn._assemble._cache_size() == assembled
    for r in done:
        for i in range(r.images.shape[0]):
            ref = _lax_model_ref(model, params, jnp.asarray(
                r.images[i:i + 1], jnp.float32))
            np.testing.assert_allclose(r.out[i], np.asarray(ref)[0],
                                       rtol=2e-3, atol=2e-3)


def test_only_unsharded_batches_over_the_threshold_are_fed(tiny):
    """The gate reads a batch's packed bytes: under ``FEED_BYTES`` (the
    tests' tiny geometries, an interactive 8-image 224x224 batch) and
    on a sharded frontend, batches take the inline path."""
    import types

    from repro.serve import frontend
    feeds = AsyncServeFrontend._feeds
    image = 224 * 224 * 3 * 4

    def progs(shape, mesh=None):
        return types.SimpleNamespace(
            mesh=mesh, image_shape=shape,
            input_dtype=lambda: np.dtype(np.float32))
    assert not feeds(progs((8, 8, 3)), 32)
    assert not feeds(progs((224, 224, 3)), 8)
    assert 8 * image < frontend.FEED_BYTES < 32 * image
    assert feeds(progs((224, 224, 3)), 32)
    assert not feeds(progs((224, 224, 3), mesh=object()), 32)


def test_feed_path_under_a_short_switch_interval_serves_every_unit_once(
        rng, tiny, monkeypatch):
    """Stress: the serve thread submits and polls while the feeder
    feeds, the interpreter switching threads every 10 us; every request
    completes once, batches are harvested in dispatch order, and the
    outputs are the inline path's under the same loop."""
    import sys

    from repro.serve import frontend
    model, params = tiny
    sizes = [int(n) for n in rng.integers(1, 6, size=40)]
    reqs = {i: rng.normal(size=(n, 8, 8, 3)).astype(np.float32)
            for i, n in enumerate(sizes)}

    def serve(feed_bytes):
        monkeypatch.setattr(frontend, "FEED_BYTES", feed_bytes)
        fe = AsyncServeFrontend(model, params, {(8, 8, 3): (2, 4)},
                                max_wait_ms=0.0, pipeline_depth=3)
        fe.warmup()
        done = []
        try:
            for rid, x in reqs.items():
                fe.submit(ServeRequest(rid=rid, images=x))
                done += fe.poll()
            done += fe.run()
        finally:
            fe.close()
        return fe, done
    _, want = serve(frontend.FEED_BYTES)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        fe, done = serve(0)
    finally:
        sys.setswitchinterval(old)
    assert fe.stats()["slices"] > fe.stats()["batches"]     # it fed
    assert [r.rid for r in done] == [r.rid for r in want]
    assert sorted(r.rid for r in done) == list(range(len(sizes)))
    assert fe.stats()["served"] == len(sizes)
    assert fe.stats()["max_inflight"] == 3
    for a, b in zip(done, want):
        np.testing.assert_array_equal(a.out, b.out)
    ids = [b.batch_id for b in fe.telemetry.batches]
    assert ids == sorted(ids)           # harvested in dispatch order
