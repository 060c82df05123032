"""Packing/telemetry invariants shared by BOTH schedulers.

Property-tests the contracts the drain engine (CnnServeEngine) and the
continuous-batching frontend (AsyncServeFrontend) must agree on, over
randomized request mixes and bucket sets:

* every submitted image is served exactly once (no drops, no double
  serves — outputs match a per-image marker exactly);
* every dispatched batch pads fewer slots than the smallest bucket
  (padding only ever rides the smallest bucket's tail);
* telemetry percentile rollups are monotone (p99 >= p95 >= p50).

One tiny model/jit-program set is shared across examples (module-scoped
engines would hide packing bugs, so engines are fresh per example — but
the model's plan memo and jit caches keep re-runs cheap).
"""
import jax
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # deterministic fallback; see _hypothesis_compat
    from _hypothesis_compat import given, settings, strategies as st

from repro.models.cnn import SimpleCNN
from repro.serve.cnn import CnnServeEngine, ImageRequest
from repro.serve.frontend import SERVED, AsyncServeFrontend, ServeRequest
from repro.serve.telemetry import rollup_percentiles

HW = 6
_MODEL = SimpleCNN([(1, 1, 3, 1)], num_classes=4)
_PARAMS = _MODEL.init(jax.random.PRNGKey(0))

# head weights are fixed; a per-image constant input yields a distinct,
# reproducible output row per marker value, so "served exactly once with
# the right result" is checkable without a conv reference
_BUCKET_SETS = [(1,), (2,), (1, 3), (2, 4), (1, 2, 4)]


def _marked_images(sizes):
    """Requests whose image i of request r is constant-filled with a
    unique marker — output rows identify their source image."""
    reqs, marker = [], 1
    for rid, n in enumerate(sizes):
        imgs = np.zeros((n, HW, HW, 3), np.float32)
        for i in range(n):
            imgs[i] = marker
            marker += 1
        reqs.append((rid, imgs))
    return reqs


def _expected_row(marker):
    x = np.full((1, HW, HW, 3), float(marker), np.float32)
    return np.asarray(_MODEL.apply(_PARAMS, x))[0]


def _check_served_exactly_once(reqs):
    for rid, imgs, out in reqs:
        assert out is not None, f"request {rid} never served"
        assert out.shape[0] == imgs.shape[0]
        for i in range(imgs.shape[0]):
            np.testing.assert_allclose(
                out[i], _expected_row(imgs[i, 0, 0, 0]),
                rtol=3e-4, atol=3e-4,
                err_msg=f"request {rid} image {i} wrong/missing result")


@settings(max_examples=8, deadline=None)
@given(st.sampled_from(_BUCKET_SETS),
       st.tuples(*[st.integers(1, 5)] * 3))
def test_drain_engine_packing_invariants(buckets, sizes):
    eng = CnnServeEngine(_MODEL, _PARAMS, (HW, HW, 3), buckets=buckets)
    reqs = [ImageRequest(rid=rid, images=imgs)
            for rid, imgs in _marked_images(sizes)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == len(sizes)
    _check_served_exactly_once([(r.rid, r.images, r.out) for r in reqs])
    assert eng.stats["images"] == sum(sizes)
    assert eng.stats["requests"] == len(sizes)
    # padding only rides the smallest bucket's final short batch, so
    # padded slots per batch (and in a drain: per run) < smallest bucket
    assert eng.stats["padded_slots"] < min(buckets)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from(_BUCKET_SETS),
       st.tuples(*[st.integers(1, 5)] * 3),
       st.sampled_from([1, 2, 3]))
def test_frontend_packing_invariants(buckets, sizes, depth):
    fe = AsyncServeFrontend(_MODEL, _PARAMS, {(HW, HW, 3): buckets},
                            pipeline_depth=depth)
    reqs = [ServeRequest(rid=rid, images=imgs)
            for rid, imgs in _marked_images(sizes)]
    for r in reqs:
        fe.submit(r)
    done = fe.run()
    assert sorted(r.rid for r in done) == list(range(len(sizes)))
    assert all(r.status == SERVED for r in done)
    _check_served_exactly_once([(r.rid, r.images, r.out) for r in reqs])
    st_ = fe.stats()
    assert st_["images"] == sum(sizes)
    # the frontend invariant is per BATCH, visible in the batch traces
    for b in fe.telemetry.batches:
        assert b.padded < min(buckets), (b.bucket, b.padded)
        assert b.units + b.padded == b.bucket
    assert st_["max_inflight"] <= depth
    lat = st_["latency_ms"]
    for stage, ps in lat.items():
        assert ps["p50"] <= ps["p95"] <= ps["p99"], stage


@settings(max_examples=20, deadline=None)
@given(st.tuples(*[st.integers(0, 10_000)] * 7))
def test_rollup_percentiles_monotone(samples):
    """p99 >= p95 >= p50 for ANY latency series (interpolated
    percentiles are monotone in q by construction)."""
    xs = [s / 7.0 for s in samples]
    ps = rollup_percentiles(xs)
    assert ps["p50"] <= ps["p95"] <= ps["p99"]
    assert min(xs) <= ps["p50"] and ps["p99"] <= max(xs)


def test_rollup_percentiles_monotone_on_tied_tail():
    """A tied tail interpolates to exactly the tied value at every q
    (the weighted-sum form read p95 one ulp above p99 here)."""
    xs = [s / 7.0 for s in (0, 0, 0, 0, 0, 8735, 8735)]
    ps = rollup_percentiles(xs)
    assert ps["p95"] == ps["p99"] == xs[-1]


def test_rollup_percentiles_rejects_empty():
    with pytest.raises(ValueError):
        rollup_percentiles([])
