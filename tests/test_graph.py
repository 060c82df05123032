"""Graph plan layer (core/graph.py) + batch-bucketed CNN serving."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import convspec as cs
from repro.core import cuconv as cc
from repro.core import graph as g
from repro.models.cnn import SimpleCNN, squeezenet_like
from repro.serve.cnn import CnnServeEngine, ImageRequest


@pytest.fixture(autouse=True)
def _hermetic_caches(tmp_path, monkeypatch):
    """Point both persisted plan stores (autotune.json, graphplans.json)
    at an empty per-test dir so other runs on this machine can't leak."""
    from repro.core import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "cache"))
    autotune.clear_cache()
    g.clear_cache()
    yield
    autotune.clear_cache()
    g.clear_cache()


TINY = [(3, 3, 8, 2), (1, 1, 4, 1), (3, 3, 6, 1)]


def _lax_model_ref(model, params, x):
    """Unbatched-library reference for the whole model (conv -> bias ->
    relu per block, GAP, head)."""
    y = x
    for p, (kh, kw, co, s) in zip(params["convs"], model.spec):
        y = jax.nn.relu(cc.conv_lax(y, p["w"], s, "same") + p["b"])
    return y.mean(axis=(1, 2)) @ params["head"]


# ---------------------------------------------------------------------------
# ConvGraph

def test_graph_chain_geometry():
    gph = g.ConvGraph.chain(TINY, (2, 16, 16, 3))
    assert len(gph) == 3
    assert gph.in_shape == (2, 16, 16, 3)
    assert gph.nodes[0].out_shape == (2, 8, 8, 8)     # stride-2 halves H/W
    assert gph.nodes[1].in_shape == gph.nodes[0].out_shape
    assert gph.out_shape == (2, 8, 8, 6)
    assert all(s.epilogue == "bias_relu" for s in gph.nodes)
    sig = gph.signature()
    assert sig == g.ConvGraph.chain(TINY, (2, 16, 16, 3)).signature()
    assert sig != g.ConvGraph.chain(TINY, (1, 16, 16, 3)).signature()


def test_graph_rejects_broken_chain():
    a = cs.ConvSpec((1, 8, 8, 3), (3, 3, 3, 4), (1, 1), (1, 1))
    b = cs.ConvSpec((1, 4, 4, 4), (1, 1, 4, 2))
    with pytest.raises(ValueError):
        g.ConvGraph((a, b))


# ---------------------------------------------------------------------------
# GraphPlan resolution, cache, explain

def test_graph_cache_roundtrip_zero_replans():
    """A warm process reconstructs the program from graphplans.json with
    ZERO per-node plan() resolutions."""
    gph = g.ConvGraph.chain(TINY, (1, 16, 16, 3))
    gp1 = g.plan_graph(gph)
    assert gp1.source == "resolved"
    assert g._STORE.path().exists()
    g.clear_cache()                       # simulate a fresh process
    cs.reset_plan_stats()
    gp2 = g.plan_graph(gph)
    assert gp2.source == "graph_cache"
    assert cs.PLAN_STATS["resolutions"] == 0
    assert ([p.algorithm for p in gp2.node_plans]
            == [p.algorithm for p in gp1.node_plans])
    assert all(p.source == "graph_cache" for p in gp2.node_plans)


def test_plan_graph_use_cache_false_touches_no_store():
    gph = g.ConvGraph.chain(TINY, (1, 16, 16, 3))
    gp = g.plan_graph(gph, use_cache=False)
    assert gp.source == "resolved"
    assert g._STORE.get(g._graph_key(gph, gp.backend)) is None


def test_forced_graph_bypasses_cache():
    gph = g.ConvGraph.chain(TINY, (1, 16, 16, 3))
    g.plan_graph(gph)                     # persist the auto choice
    gp = g.plan_graph(gph, force="lax")
    assert gp.source == "forced"
    assert all(p.algorithm == "lax" for p in gp.node_plans)
    # the forced run must not have clobbered the persisted auto entry
    g.clear_cache()
    assert g.plan_graph(gph).source == "graph_cache"


def test_explain_lists_every_node():
    gph = g.ConvGraph.chain(TINY, (1, 16, 16, 3))
    gp = g.plan_graph(gph)
    txt = gp.explain()
    assert gph.signature() in txt
    assert len(txt.splitlines()) == len(gph) + 1
    for p in gp.node_plans:
        assert p.algorithm in txt


def test_measured_winner_invalidates_graph_cache_entry():
    """plan()'s measured > heuristic precedence survives the graph layer:
    a winner recorded AFTER the graph entry was persisted forces a
    re-resolve instead of serving the stale heuristic program forever."""
    from repro.core import autotune
    gph = g.ConvGraph.chain([(1, 1, 4, 1)], (1, 6, 6, 3))
    gp1 = g.plan_graph(gph)
    assert gp1.source == "resolved"
    other = next(a for a in ("lax", "cuconv_pallas")
                 if a != gp1.node_plans[0].algorithm)
    autotune.record_best(gph.nodes[0], gp1.backend, other)
    g.clear_cache()
    gp2 = g.plan_graph(gph)
    assert gp2.source == "resolved"          # stale entry was dropped
    assert gp2.node_plans[0].algorithm == other
    assert gp2.node_plans[0].source == "measured"
    g.clear_cache()                          # re-persisted entry now agrees
    assert g.plan_graph(gph).source == "graph_cache"


def test_warmup_measure_rejects_foreign_backend():
    """Measuring on the default backend but recording under another
    backend's key would silently discard the sweep — refuse instead."""
    other = "tpu" if jax.default_backend() != "tpu" else "cpu"
    gp = g.plan_graph(g.ConvGraph.chain([(1, 1, 4, 1)], (1, 6, 6, 3)),
                      backend=other)
    with pytest.raises(ValueError):
        gp.warmup(measure=True)


def test_warmup_measure_records_winners():
    gph = g.ConvGraph.chain([(1, 1, 4, 1)], (1, 6, 6, 3))
    gp = g.plan_graph(gph)
    stats = gp.warmup(measure=True, repeats=1)
    assert len(stats["nodes"]) == 1
    assert stats["nodes"][0]["source"] == "measured"
    from repro.core import autotune
    assert autotune.cached_best(gph.nodes[0]) is not None


# ---------------------------------------------------------------------------
# SimpleCNN over GraphPlan

def test_planned_once_then_zero_replans(rng):
    """Acceptance: warmup() then N inference calls triggers zero
    additional plan() resolutions, and outputs match the lax reference."""
    model = squeezenet_like()
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.asarray(rng.normal(size=(1, 32, 32, 3)), jnp.float32)
    gp = model.graph_plan((1, 32, 32, 3))
    gp.warmup()
    cs.reset_plan_stats()
    for _ in range(3):
        y = model.apply(params, x)        # eager: re-enters apply each time
    assert cs.PLAN_STATS["resolutions"] == 0
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_lax_model_ref(model, params, x)),
        rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("algorithm", ["auto", "lax"])
def test_model_apply_matches_reference(rng, algorithm):
    model = SimpleCNN(TINY, num_classes=5)
    params = model.init(jax.random.PRNGKey(1))
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    y = jax.jit(lambda p, xx: model.apply(p, xx, algorithm=algorithm))(
        params, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(_lax_model_ref(model, params, x)),
        rtol=3e-4, atol=3e-4, err_msg=algorithm)


# ---------------------------------------------------------------------------
# CnnServeEngine

def test_serve_mixed_stream_buckets_and_outputs(rng):
    """Acceptance: a mixed-size request stream is served through at most
    the configured buckets, outputs matching the unbatched lax reference."""
    model = SimpleCNN(TINY, num_classes=4)
    params = model.init(jax.random.PRNGKey(0))
    eng = CnnServeEngine(model, params, (16, 16, 3), buckets=(1, 2, 4))
    eng.warmup()
    sizes = [1, 3, 2, 5, 1]
    reqs = [ImageRequest(rid=i, images=rng.normal(
        size=(n, 16, 16, 3)).astype(np.float32))
        for i, n in enumerate(sizes)]
    for r in reqs:
        eng.submit(r)
    cs.reset_plan_stats()
    done = eng.run()
    assert cs.PLAN_STATS["resolutions"] == 0    # warm engine: no re-plans
    assert len(done) == len(sizes) and all(r.done for r in done)
    assert set(eng.compiled_buckets) <= set(eng.buckets)
    assert eng.stats["images"] == sum(sizes)
    for r in reqs:
        for i in range(r.images.shape[0]):
            ref = _lax_model_ref(model, params,
                                 jnp.asarray(r.images[i:i + 1]))
            np.testing.assert_allclose(r.out[i], np.asarray(ref)[0],
                                       rtol=3e-4, atol=3e-4,
                                       err_msg=f"req {r.rid} image {i}")


def test_serve_pads_short_tail(rng):
    model = SimpleCNN([(1, 1, 4, 1)], num_classes=3)
    params = model.init(jax.random.PRNGKey(0))
    eng = CnnServeEngine(model, params, (8, 8, 3), buckets=(4,))
    eng.submit(ImageRequest(                       # single (H, W, C) image
        rid=0, images=rng.normal(size=(8, 8, 3)).astype(np.float32)))
    done = eng.run()
    assert done[0].out.shape == (1, 3)
    assert eng.stats["padded_slots"] == 3
    assert eng.compiled_buckets == (4,)
    ref = _lax_model_ref(model, params, jnp.asarray(done[0].images))
    np.testing.assert_allclose(done[0].out, np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_serve_measured_warmup_rebuilds_programs(rng):
    """warmup(measure=True) after programs were already compiled must not
    keep serving the stale traces: every bucket program is rebuilt."""
    model = SimpleCNN([(1, 1, 4, 1)], num_classes=3)
    params = model.init(jax.random.PRNGKey(0))
    eng = CnnServeEngine(model, params, (6, 6, 3), buckets=(1, 2))
    eng.warmup()
    fns_before = dict(eng._fns)
    eng.warmup(measure=True)
    assert set(eng._fns) == set(fns_before)
    assert all(eng._fns[b] is not fns_before[b] for b in fns_before)
    eng.submit(ImageRequest(rid=0, images=rng.normal(
        size=(2, 6, 6, 3)).astype(np.float32)))
    done = eng.run()
    ref = _lax_model_ref(model, params, jnp.asarray(done[0].images))
    np.testing.assert_allclose(done[0].out, np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_serve_retry_after_mid_drain_failure(rng):
    """The retry contract run()'s comment promises, pinned: a bucket
    program that raises mid-drain leaves engine.queue intact, and a
    retried run() serves every image exactly once (outputs rewrite
    idempotently)."""
    model = SimpleCNN([(1, 1, 4, 1)], num_classes=3)
    params = model.init(jax.random.PRNGKey(0))
    eng = CnnServeEngine(model, params, (8, 8, 3), buckets=(2,))
    eng.warmup()
    reqs = [ImageRequest(rid=i, images=rng.normal(
        size=(n, 8, 8, 3)).astype(np.float32))
        for i, n in enumerate([2, 3])]          # 5 units -> 3 batches
    for r in reqs:
        eng.submit(r)
    real, calls = eng._fns[2], {"n": 0}

    def boom(params, xb):
        calls["n"] += 1
        if calls["n"] == 2:                     # fail on the SECOND batch
            raise RuntimeError("injected mid-drain failure")
        return real(params, xb)

    eng._fns[2] = boom
    with pytest.raises(RuntimeError, match="mid-drain"):
        eng.run()
    assert eng.queue == reqs                    # nothing lost, FIFO order
    assert not any(r.done for r in reqs)
    eng._fns[2] = real                          # "transient" fault clears
    done = eng.run()
    assert eng.queue == [] and [r.rid for r in done] == [0, 1]
    assert all(r.done for r in done)
    assert eng.stats["requests"] == 2
    for r in reqs:                              # exactly once: every row
        assert r.out.shape == (r.images.shape[0], 3)
        for i in range(r.images.shape[0]):
            ref = _lax_model_ref(model, params,
                                 jnp.asarray(r.images[i:i + 1]))
            np.testing.assert_allclose(r.out[i], np.asarray(ref)[0],
                                       rtol=3e-4, atol=3e-4,
                                       err_msg=f"req {r.rid} image {i}")


def test_serve_rejects_wrong_geometry(rng):
    model = SimpleCNN([(1, 1, 4, 1)], num_classes=3)
    params = model.init(jax.random.PRNGKey(0))
    eng = CnnServeEngine(model, params, (8, 8, 3))
    with pytest.raises(ValueError):
        eng.submit(ImageRequest(
            rid=0, images=rng.normal(size=(4, 4, 3)).astype(np.float32)))
    with pytest.raises(ValueError):
        CnnServeEngine(model, params, (8, 8, 3), buckets=())
