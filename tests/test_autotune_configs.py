"""Launch-config tuning layer (DESIGN.md §9): candidate spaces, the
(algorithm, config) plan pair, the versioned autotune cache.

Covers the acceptance surface of the tuning PR:

  * numerics — every candidate launch config of every executor, over a
    grid of specs, matches the fp32 library reference (interpret mode);
  * feasibility — each Pallas executor exposes >= 3 VMEM-feasible
    candidates on the paper's profiled table-3/4 shapes;
  * forcing — an infeasible forced config raises a clear error naming
    executor, config and spec;
  * staleness — a persisted config invalid under the current geometry
    (e.g. ``rows`` > OH) or an unversioned/foreign-schema cache entry
    is dropped and re-resolved, never served;
  * round-trip — ``plan(tune="full")`` measures >= 3 feasible
    candidates, persists the winner under the versioned schema, and a
    later plan replays it with ZERO re-measurement (MEASURE_STATS).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core import convspec as cs
from repro.core import cuconv as cc
from repro.core import executors as ex
from repro.core.plancache import cache_dir

TOLS = {"float32": dict(rtol=3e-4, atol=3e-4),
        "bfloat16": dict(rtol=3e-2, atol=3e-2)}


@pytest.fixture(autouse=True)
def _hermetic_autotune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "cache"))
    autotune.clear_cache()
    autotune.reset_measure_stats()
    yield
    autotune.clear_cache()


# spec grid: kernel size / stride / padding / epilogue / dtype coverage
# for the per-candidate numerics sweep (small shapes: interpret mode)
GEOMS = [
    ((1, 8, 8, 6), (3, 3), 4, (1, 1), (1, 1), "bias_relu"),
    ((2, 9, 9, 5), (3, 3), 4, (2, 2), (1, 1), "none"),
    ((1, 6, 6, 8), (1, 1), 4, (1, 1), (0, 0), "bias"),
    ((1, 7, 7, 4), (5, 5), 3, (1, 1), (2, 2), "none"),
    ((1, 12, 5, 6), (3, 3), 5, (2, 1), (1, 1), "relu"),
]

# the paper's profiled configurations each Pallas executor must expose
# a real tuning space on (table 3 A for a 1x1 layer, table 4 A/B for
# the KxK layers)
T3_A = cs.ConvSpec((1, 7, 7, 832), (1, 1, 832, 256))
T4_A = cs.ConvSpec((1, 7, 7, 192), (3, 3, 192, 384), (1, 1), (1, 1))
T4_B = cs.ConvSpec((1, 13, 13, 384), (3, 3, 384, 384), (1, 1), (1, 1))

PALLAS = ("cuconv_pallas", "winograd_pallas")


def _spec(geom, dtype="float32"):
    in_shape, (kh, kw), m, stride, padding, epi = geom
    return cs.ConvSpec(in_shape, (kh, kw, in_shape[3], m), stride, padding,
                       dtype, epi)


def _operands(spec, rng):
    dtype = jnp.dtype(spec.dtype)
    x = jnp.asarray(rng.normal(size=spec.in_shape), jnp.float32) \
        .astype(dtype)
    w = jnp.asarray(rng.normal(size=spec.filter_shape), jnp.float32) \
        .astype(dtype)
    b = (jnp.asarray(rng.normal(size=(spec.filter_shape[3],)), jnp.float32)
         .astype(dtype) if spec.has_bias else None)
    return x, w, b


def _f32_ref(spec, x, w, b):
    y = cc.conv_lax(x.astype(jnp.float32), w.astype(jnp.float32),
                    spec.stride, spec.padding, groups=spec.groups)
    if spec.has_bias:
        y = y + b.astype(jnp.float32)
    if spec.wants_relu:
        y = jax.nn.relu(y)
    return np.asarray(y)


# ---------------------------------------------------------------------------
# candidate space declarations

def test_candidate_zero_is_the_historical_geometry():
    """Candidate 0 of every tunable executor is the hard-coded pre-tuning
    geometry (clamped to the spec), so nothing regresses by default."""
    fused = ex.get("cuconv_pallas").configs(T4_A)[0]
    assert fused.as_dict() == {"tm": 128, "rows": 1}
    # the int8 GEMM's candidate 0: the default tiles, tp clamped to
    # N*OH*OW and tc to KH*KW*C
    i8 = ex.get("cuconv_int8").configs(T4_A)[0]
    assert i8.as_dict() == {"tp": 49, "tm": 128, "tc": 512}
    # winograd_pallas candidate 0 is the F(2,3) variant at the default
    # tiles (rows clamped to the spec's tile rows: ceil(7/2) = 4)
    wg = ex.get("winograd_pallas").configs(T4_A)[0]
    assert wg.as_dict() == {"m": 2, "rows": 4, "tm": 128, "tc": 128}


@pytest.mark.parametrize("name,spec", [
    ("cuconv_pallas", T4_A), ("cuconv_pallas", T4_B),
    ("winograd_pallas", T4_A), ("winograd_pallas", T4_B),
])
def test_pallas_executors_expose_three_feasible_candidates(name, spec):
    """Acceptance: >= 3 VMEM-feasible candidate configs per Pallas
    executor on the paper's profiled shapes (pruned through
    config_supports BEFORE any measurement)."""
    exe = ex.get(name)
    feasible = [c for c in exe.configs(spec)
                if exe.config_supports(spec, c)[0]]
    assert len(feasible) >= 3, (name, [c.key() for c in feasible])
    # candidates are deduplicated after clamping
    assert len(set(feasible)) == len(feasible)


def test_untunable_executors_have_one_empty_config():
    for name in ("lax",):
        exe = ex.get(name)
        assert exe.tunable == ()
        (only,) = exe.configs(T4_A)
        assert not only and only.as_dict() == {}
        assert exe.default_config(T4_A) == only


def test_default_config_is_vmem_feasible_and_model_ranked():
    """default_config picks a feasible candidate by the executor's
    config-cost model — never one the VMEM budget rejects."""
    for name in PALLAS:
        exe = ex.get(name)
        for spec in (T4_A, T3_A):
            if not exe.supports(spec)[0]:
                continue
            cfg = exe.default_config(spec)
            ok, why = exe.config_supports(spec, cfg)
            assert ok, (name, cfg.key(), why)
            # the model never ranks a feasible candidate above a cheaper one
            feas = [c for c in exe.configs(spec)
                    if exe.config_supports(spec, c)[0]]
            best = min(exe.config_cost(spec, c) for c in feas)
            assert exe.config_cost(spec, cfg) == best


# ---------------------------------------------------------------------------
# cuconv_pallas: launch configs that tile the output exactly

# the output rows and channels the benchmark's configurations serve
# (ResNet-50, SqueezeNet 1.0, ConvNeXt-T at 224x224)
SERVED_OH = (56, 28, 14, 7, 54, 27, 13)
SERVED_M = (16, 48, 64, 128, 192, 256, 384, 512, 768, 1000, 1024, 1536,
            2048, 3072)
# (OH, M, C) with no feasible exact config: M = 1000 has no 128-lane
# divisor, and its one whole-extent tile holds a (C x 1000) weight
# block that busts the VMEM budget at C = 4096
NO_EXACT_CONFIG = {(oh, 1000, 4096) for oh in SERVED_OH}


@pytest.mark.parametrize("target,oh,rows", [
    (8, 56, 8), (8, 28, 7), (8, 14, 7), (8, 7, 7), (8, 54, 9), (8, 27, 9),
    (8, 13, 13), (4, 54, 3), (2, 27, 3), (4, 13, 4), (8, 53, 8)])
def test_row_target_takes_the_nearest_divisor_of_oh(target, oh, rows):
    """Ties go to the larger divisor; a target whose nearest divisor is
    below half of it stays padded."""
    assert ex._exact_rows(oh, target) == rows


@pytest.mark.parametrize("target,m,tm", [
    (512, 768, 384), (512, 1000, 1000), (256, 768, 256), (512, 1536, 512),
    (128, 64, 64), (512, 192, 192), (512, 640, 128)])
def test_lane_target_takes_the_largest_128_multiple_dividing_m(target, m,
                                                               tm):
    assert ex._exact_tm(m, target) == tm


@pytest.mark.parametrize("m", SERVED_M)
@pytest.mark.parametrize("oh", SERVED_OH)
def test_default_config_tiles_the_output_exactly(oh, m):
    """Wherever a feasible config has ``rows | OH`` and ``tm | M``, the
    model-chosen default has too; elsewhere the case is a named
    exception and the default is still feasible."""
    exe = ex.get("cuconv_pallas")
    for c in (256, 4096):
        spec = cs.ConvSpec((32, oh, oh, c), (1, 1, c, m))
        feasible = [cfg for cfg in exe.configs(spec)
                    if exe.config_supports(spec, cfg)[0]]
        exact = [cfg for cfg in feasible
                 if exe.padded_share(spec, cfg) == 0]
        assert bool(exact) == ((oh, m, c) not in NO_EXACT_CONFIG)
        cfg = exe.default_config(spec)
        assert cfg in feasible, cfg.key()
        if exact:
            assert oh % cfg["rows"] == 0 and m % min(cfg["tm"], m) == 0
            assert exe.padded_share(spec, cfg) == 0, cfg.key()
        else:
            assert exe.padded_share(spec, cfg) > 0


def test_padded_config_never_ties_an_exact_one():
    """At OH 28 and M 768 the padded rows=8 and tm=512 blocks take as
    many grid steps as rows=7 and tm=384, and cost more."""
    exe = ex.get("cuconv_pallas")
    spec = cs.ConvSpec((32, 28, 28, 192), (1, 1, 192, 768))
    cost = lambda **cfg: exe.config_cost(spec, ex.LaunchConfig.of(cfg))
    assert cost(rows=7, tm=384) < cost(rows=8, tm=384)
    assert cost(rows=7, tm=384) < cost(rows=7, tm=512)
    assert exe.default_config(spec).as_dict() == {"rows": 7, "tm": 384}
    assert exe.padded_share(spec, {"rows": 8, "tm": 512}) == pytest.approx(
        1 - 28 * 768 / (32 * 1024))


# ---------------------------------------------------------------------------
# numerics: every candidate config executes exactly

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", PALLAS)
def test_every_candidate_config_matches_lax(rng, name, dtype):
    exe = ex.get(name)
    ran = 0
    for geom in GEOMS:
        spec = _spec(geom, dtype)
        if not exe.supports(spec)[0]:
            continue
        x, w, b = _operands(spec, rng)
        want = _f32_ref(spec, x, w, b)
        for cfg in exe.configs(spec):
            if not exe.config_supports(spec, cfg)[0]:
                continue
            ran += 1
            got = exe.execute(spec, x, w, bias=b, config=cfg)
            np.testing.assert_allclose(
                np.asarray(got, np.float32), want,
                err_msg=f"{name} cfg[{cfg.key()}] {spec.key()}",
                **TOLS[dtype])
    assert ran > 0, f"{name} ran no candidate configs over the grid"


# ---------------------------------------------------------------------------
# forcing

def test_forced_infeasible_config_raises_naming_executor_config_spec():
    spec = _spec(GEOMS[0])                              # OH = 8
    with pytest.raises(ValueError) as e:
        cs.plan(spec, force="cuconv_pallas", config={"tm": 128, "rows": 64})
    msg = str(e.value)
    assert "cuconv_pallas" in msg and "rows" in msg and spec.key() in msg
    # a config whose working set blows the VMEM budget is refused too
    big = cs.ConvSpec((1, 8, 300, 1024), (3, 3, 1024, 256),
                      (1, 1), (1, 1))
    assert ex.get("cuconv_pallas").supports(big)[0]     # default cfg fits
    with pytest.raises(ValueError, match="VMEM"):
        cs.plan(big, force="cuconv_pallas", config={"tm": 256, "rows": 8})
    # unknown dims are named, not silently ignored
    with pytest.raises(ValueError, match="tunable"):
        cs.plan(spec, force="cuconv_pallas", config={"warp": 4})
    # untunable executors refuse any non-empty config
    with pytest.raises(ValueError, match="lax"):
        cs.plan(spec, force="lax", config={"tm": 128})


def test_forced_infeasible_config_raises_for_new_executors():
    """The PR-10 executors honor the same loud-raise contract: a forced
    config outside the tuning space names executor, config and spec."""
    # F(m,3) variant is a config dim but only m in {2, 4} exists
    with pytest.raises(ValueError) as e:
        cs.plan(T4_A, force="winograd_pallas",
                config={"m": 3, "rows": 4, "tm": 128, "tc": 128})
    msg = str(e.value)
    assert "winograd_pallas" in msg and "m=3" in msg and T4_A.key() in msg
    # oversized tiles blow the (unclamped) VMEM model and are refused
    with pytest.raises(ValueError, match="VMEM"):
        cs.plan(T4_B, force="winograd_pallas",
                config={"m": 4, "rows": 4, "tm": 128, "tc": 1024})
    # the NHWC output is a strided store: at most 128 output lanes
    with pytest.raises(ValueError, match="tm=256"):
        cs.plan(T4_B, force="winograd_pallas",
                config={"m": 2, "rows": 4, "tm": 256, "tc": 128})


def test_forced_valid_config_rides_the_plan(rng):
    spec = _spec(GEOMS[0])
    p = cs.plan(spec, force="cuconv_pallas", config={"tm": 4, "rows": 2})
    assert p.config_source == "forced"
    assert p.config.as_dict() == {"tm": 4, "rows": 2}
    assert "cfg[forced]=rows=2,tm=4" in p.explain()
    x, w, b = _operands(spec, rng)
    np.testing.assert_allclose(np.asarray(p(x, w, b), np.float32),
                               _f32_ref(spec, x, w, b), **TOLS["float32"])


# ---------------------------------------------------------------------------
# staleness + schema versioning

def test_stale_persisted_config_is_reresolved_not_served():
    """A persisted config that a geometry change invalidated (rows > OH)
    is dropped at resolve time; the plan gets a valid config instead."""
    spec = _spec(GEOMS[0])                              # OH = 8
    autotune.record_best(spec, "cpu", "cuconv_pallas",
                         config={"tm": 128, "rows": 64})
    p = cs.plan(spec, backend="cpu")
    assert p.algorithm == "cuconv_pallas"               # winner still serves
    assert p.config_source == "default"                 # ...config does not
    ok, _ = ex.get("cuconv_pallas").config_supports(spec, p.config)
    assert ok
    assert p.config.get("rows", 1) <= spec.out_shape[1]


@pytest.mark.parametrize("name,spec,stale", [
    ("winograd_pallas", T4_A, {"m": 3, "rows": 4, "tm": 128, "tc": 128}),
    ("winograd_pallas", T4_B, {"m": 4, "rows": 4, "tm": 128, "tc": 1024}),
])
def test_stale_persisted_config_self_heals_for_new_executors(name, spec,
                                                             stale):
    """PR-5 contract extends to the PR-10 executors: an invalid persisted
    config (schema drift, VMEM-model tightening) is dropped at resolve
    time and the winner re-serves on its default config."""
    autotune.record_best(spec, "cpu", name, config=stale)
    p = cs.plan(spec, backend="cpu", force=name)
    assert p.algorithm == name
    assert p.config_source == "default"
    ok, why = ex.get(name).config_supports(spec, p.config)
    assert ok, why


def test_persisted_winograd_tt_config_is_reresolved():
    """A winograd_pallas config persisted before ``rows`` replaced
    ``tt`` (tiles per block) names a dim the executor no longer tunes:
    it is dropped at resolve time, not served and not raised on."""
    stale = {"m": 2, "tt": 128, "tm": 128, "tc": 128}
    autotune.record_best(T4_B, "cpu", "winograd_pallas", config=stale)
    p = cs.plan(T4_B, backend="cpu", force="winograd_pallas")
    assert p.algorithm == "winograd_pallas"
    assert p.config_source == "default"
    assert "tt" not in p.config and "rows" in p.config
    ok, why = ex.get("winograd_pallas").config_supports(T4_B, p.config)
    assert ok, why


def test_config_never_leaks_across_algorithms():
    """A config measured for one executor is not served when another
    executor wins the spec."""
    spec = _spec(GEOMS[0])
    autotune.record_best(spec, "cpu", "cuconv_pallas",
                         config={"tm": 4, "rows": 2})
    assert autotune.cached_config(spec, "cpu", "cuconv_pallas") is not None
    assert autotune.cached_config(spec, "cpu", "lax") is None


def test_unversioned_and_foreign_schema_entries_are_dropped():
    """Satellite: autotune.json is schema-versioned like graphplans.json
    — the pre-config era's bare algorithm strings and foreign schemas
    are never misdecoded into the (algorithm, config) shape."""
    spec = _spec(GEOMS[0])
    key = autotune._key(spec, "cpu")
    autotune._STORE.put(key, "lax")                     # v1: bare string
    assert autotune.cached_best(spec, "cpu") is None
    assert autotune.cached_config(spec, "cpu") is None
    autotune._STORE.put(key, {"schema": 99, "algorithm": "lax"})
    assert autotune.cached_best(spec, "cpu") is None
    autotune._STORE.put(key, {"algorithm": "lax"})      # unversioned dict
    assert autotune.cached_best(spec, "cpu") is None
    # plan() falls back to the heuristic tier, not a misdecoded entry
    assert cs.plan(spec, backend="cpu").source in ("heuristic", "cost")
    # a versioned entry with malformed config dims serves the algorithm
    # but drops the config
    autotune._STORE.put(key, {"schema": autotune.AUTOTUNE_SCHEMA,
                              "algorithm": "cuconv_pallas",
                              "configs": {"cuconv_pallas":
                                          {"tm": "huge"}}})
    assert autotune.cached_best(spec, "cpu") == "cuconv_pallas"
    assert autotune.cached_config(spec, "cpu", "cuconv_pallas") is None


def test_algorithm_change_stops_serving_old_executors_config():
    spec = _spec(GEOMS[0])
    autotune.record_best(spec, "cpu", "cuconv_pallas",
                         config={"tm": 4, "rows": 2})
    autotune.record_best(spec, "cpu", "lax")            # algorithm changed
    assert autotune.cached_best(spec, "cpu") == "lax"
    # the new winner has no config of its own...
    assert autotune.cached_config(spec, "cpu") is None
    # ...but the old executor's measurement survives under ITS key (a
    # later forced plan of that executor still replays it)
    got = autotune.cached_config(spec, "cpu", "cuconv_pallas")
    assert got is not None and got.as_dict() == {"tm": 4, "rows": 2}


def test_forced_tune_never_overwrites_the_measured_winner(rng):
    """Tuning a pinned executor's configs (plan(force=..., tune="full"))
    records under that executor's per-algorithm slot; the genuinely
    measured algorithm winner keeps serving unforced plans."""
    spec = cs.ConvSpec((1, 6, 6, 8), (1, 1, 8, 4))
    cs.plan(spec, tune="algo")                  # real executor sweep
    winner = autotune.cached_best(spec)
    assert winner is not None
    forced = "cuconv_pallas" if winner != "cuconv_pallas" else "lax"
    p = cs.plan(spec, force=forced, tune="full")
    assert p.algorithm == forced
    # the unforced plan still serves the measured winner, not the
    # forced executor
    assert autotune.cached_best(spec) == winner
    assert cs.plan(spec).algorithm == winner


# ---------------------------------------------------------------------------
# the measured sweep + replay (the CI tuning smoke runs this class of
# test over the paper configs)

def test_plan_tune_full_measures_persists_and_replays(rng):
    """Acceptance: tune="full" sweeps >= 3 feasible candidates of the
    Pallas executor, persists the (algorithm, config) winner under the
    versioned schema, and replays it from cache with ZERO
    re-measurement."""
    spec = cs.ConvSpec((1, 7, 7, 16), (3, 3, 16, 32), (1, 1), (1, 1))
    exe = ex.get("cuconv_pallas")
    feasible = [c for c in exe.configs(spec)
                if exe.config_supports(spec, c)[0]]
    assert len(feasible) >= 3
    autotune.reset_measure_stats()
    p = cs.plan(spec, force="cuconv_pallas", tune="full")
    assert autotune.MEASURE_STATS["config_sweeps"] == 1
    assert autotune.MEASURE_STATS["timed_calls"] >= len(feasible)
    assert p.config_source == "measured"
    assert p.config in feasible
    # persisted under the versioned schema, keyed per algorithm; a
    # forced tune records NO measured-winner algorithm (none was swept)
    raw = json.loads((cache_dir() / "autotune.json").read_text())
    entry = raw[autotune._key(spec, jax.default_backend())]
    assert entry["schema"] == autotune.AUTOTUNE_SCHEMA
    assert entry["algorithm"] is None
    assert entry["configs"]["cuconv_pallas"] == p.config.as_dict()
    # replay: same pair, zero measurement — in this process and in a
    # "fresh" one (simulated by dropping the in-memory mirror)
    autotune.clear_cache()
    autotune.reset_measure_stats()
    p2 = cs.plan(spec, force="cuconv_pallas")
    assert (p2.algorithm, p2.config) == (p.algorithm, p.config)
    assert p2.config_source == "measured"
    assert autotune.MEASURE_STATS["timed_calls"] == 0
    assert autotune.MEASURE_STATS["config_sweeps"] == 0
    # the tuned plan computes the right answer
    x, w, b = _operands(spec, rng)
    np.testing.assert_allclose(np.asarray(p2(x, w), np.float32),
                               _f32_ref(spec, x, w, None),
                               **TOLS["float32"])


def test_tune_algo_then_full_compose():
    """tune="algo" records only the winner; a later tune="full" adds the
    config without re-running the executor sweep."""
    spec = cs.ConvSpec((1, 6, 6, 8), (1, 1, 8, 4))
    cs.plan(spec, tune="algo")
    best = autotune.cached_best(spec)
    assert best is not None
    assert autotune.cached_config(spec) is None or best is not None
    autotune.reset_measure_stats()
    p = cs.plan(spec, tune="full")
    assert autotune.MEASURE_STATS["algo_sweeps"] == 0   # winner cached
    assert p.algorithm == best


def test_tune_rejects_foreign_backend_and_bad_mode():
    spec = _spec(GEOMS[0])
    other = "tpu" if jax.default_backend() != "tpu" else "cpu"
    with pytest.raises(ValueError, match="backend"):
        cs.plan(spec, tune="algo", backend=other)
    with pytest.raises(ValueError, match="tune"):
        cs.plan(spec, tune="everything")


def test_measure_config_short_circuits_on_valid_persisted_config(rng):
    x = jnp.asarray(rng.normal(size=(1, 7, 7, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 8, 16)), jnp.float32)
    algo, cfg = autotune.measure_config(x, w, repeats=1,
                                        algorithm="cuconv_pallas")
    assert cfg
    autotune.reset_measure_stats()
    algo2, cfg2 = autotune.measure_config(x, w, repeats=1,
                                          algorithm="cuconv_pallas")
    assert (algo2, cfg2) == (algo, cfg)
    assert autotune.MEASURE_STATS["timed_calls"] == 0
    # an EXPLICIT candidate list is a request to measure exactly those
    # configs: it bypasses the cached hit and its winner is among them
    wanted = ({"tm": 8, "rows": 1}, {"tm": 16, "rows": 2})
    autotune.reset_measure_stats()
    _, cfg3 = autotune.measure_config(x, w, repeats=1,
                                      algorithm="cuconv_pallas",
                                      candidates=wanted)
    assert cfg3.as_dict() in [dict(d) for d in wanted]
    assert autotune.MEASURE_STATS["timed_calls"] > 0


class _BrokenTuningExecutor(ex.Executor):
    """Registered executor whose tuning-space declarations raise."""
    name = "broken_tuning_plugin"

    def configs(self, spec):
        raise RuntimeError("broken tuning space")

    def _execute(self, spec, x, w, bias, interpret, config=None):
        return cc.conv_lax(x, w, stride=spec.stride, padding=spec.padding)


def test_measure_algorithm_degrades_on_broken_tuning_declarations(rng):
    """One candidate's broken configs()/default_config() skips that
    candidate instead of crashing the whole sweep."""
    ex.register(_BrokenTuningExecutor())
    try:
        spec = _spec(GEOMS[2])
        x, w, b = _operands(spec, rng)
        best = autotune.measure_algorithm(
            x, w, stride=spec.stride, padding=spec.padding, repeats=1,
            candidates=("broken_tuning_plugin", "lax"))
        assert best == "lax"
    finally:
        ex.unregister("broken_tuning_plugin")


class _RefusedConfigExecutor(ex.Executor):
    """Registered tunable executor whose kernel fails under one launch
    config, as a kernel the backend's compiler refuses would."""
    name = "refused_config_plugin"
    tunable = ("tm",)

    def configs(self, spec):
        return (ex.LaunchConfig.of({"tm": 1}), ex.LaunchConfig.of({"tm": 2}))

    def _execute(self, spec, x, w, bias, interpret, config=None):
        if config["tm"] == 2:
            raise RuntimeError("kernel refused by the compiler")
        return cc.conv_lax(x, w, stride=spec.stride, padding=spec.padding)


def test_sweeps_record_failed_candidates(rng):
    """A candidate that raises drops out of the sweep AND is recorded in
    MEASURE_STATS["failures"] with its executor, config and error."""
    ex.register(_RefusedConfigExecutor())
    try:
        spec = _spec(GEOMS[2])
        x, w, b = _operands(spec, rng)
        algo, cfg = autotune.measure_config(
            x, w, repeats=1, algorithm="refused_config_plugin", bias=b,
            spec=spec)
        assert (algo, cfg.as_dict()) == ("refused_config_plugin", {"tm": 1})
        fails = autotune.MEASURE_STATS["failures"]
        assert len(fails) == 1
        assert fails[0]["executor"] == "refused_config_plugin"
        assert fails[0]["config"] == {"tm": 2}
        assert fails[0]["spec"] == spec.key()
        assert "kernel refused by the compiler" in fails[0]["error"]
        assert autotune.reset_measure_stats()["failures"] == fails
        assert autotune.MEASURE_STATS["failures"] == []
    finally:
        ex.unregister("refused_config_plugin")


def test_forced_tune_algo_still_runs_the_executor_sweep():
    """plan(force=..., tune="algo") is not a silent no-op: the sweep
    runs and records the UNFORCED winner for later unforced plans."""
    spec = cs.ConvSpec((1, 6, 6, 8), (1, 1, 8, 4))
    autotune.reset_measure_stats()
    p = cs.plan(spec, force="cuconv_pallas", tune="algo")
    assert p.algorithm == "cuconv_pallas"       # the pin decides this plan
    assert autotune.MEASURE_STATS["algo_sweeps"] == 1
    assert autotune.cached_best(spec) is not None


# ---------------------------------------------------------------------------
# graph layer carries configs

def test_graph_warmup_tune_full_reports_and_replays_configs():
    from repro.core.graph import plan_graph
    from repro.models.cnn import squeezenet_like
    model = squeezenet_like()
    gp = model.graph_plan((1, 16, 16, 3))
    stats = gp.warmup(tune="full", repeats=1)
    assert all("config" in r and "config_source" in r
               for r in stats["nodes"])
    # tuned configs visible in the whole-network explain table where a
    # tunable executor won
    txt = gp.explain()
    for name, p in gp.conv_plans.items():
        if p.config:
            assert f"cfg[{p.config_source}]={p.config.key()}" in txt
    # a fresh plan of the same graph reconstructs from the graph cache
    # and re-resolves each node's measured config with zero measurement
    autotune.reset_measure_stats()
    gp2 = plan_graph(gp.graph, backend=gp.backend)
    assert gp2.source == "graph_cache"
    for name, p in gp.conv_plans.items():
        assert gp2.conv_plans[name].algorithm == p.algorithm
        assert gp2.conv_plans[name].config == p.config
    assert autotune.MEASURE_STATS["timed_calls"] == 0


def test_explain_shows_tuned_multirow_config():
    """Acceptance: explain() reports the fused kernel's multi-row
    blocking with provenance."""
    spec = cs.ConvSpec((1, 7, 7, 16), (3, 3, 16, 32), (1, 1), (1, 1))
    p = cs.plan(spec, force="cuconv_pallas", config={"tm": 32, "rows": 4})
    txt = p.explain()
    assert "cfg[forced]=rows=4,tm=32" in txt
    pd = cs.plan(spec, force="cuconv_pallas")
    assert "cfg[default]=" in pd.explain()
