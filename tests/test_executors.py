"""Executor-registry conformance suite (core/executors.py).

Two invariants, parametrized over EVERY registered executor x dtype:

  (a) numerics — for every spec the executor claims to support, its
      planned execution (epilogue included) matches the fp32 library
      reference within dtype-appropriate tolerance;
  (b) capability honesty — ``plan()`` never selects an executor whose
      declared capabilities don't cover the spec, across forced /
      measured / heuristic / cost tiers and both backends.

Plus the registry API itself: registration, duplicate/unknown errors,
third-party executors participating in negotiation and cache
resolution, and the cheapest-supported cost tier.

CI runs this file as its own matrix step (Pallas interpret mode on
CPU), split by dtype, so kernel-capability regressions fail fast.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core import convspec as cs
from repro.core import cuconv as cc
from repro.core import executors as ex

TOLS = {"float32": dict(rtol=3e-4, atol=3e-4),
        "bfloat16": dict(rtol=3e-2, atol=3e-2)}

DTYPES = ("float32", "bfloat16")


@pytest.fixture(autouse=True)
def _hermetic_autotune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "cache"))
    autotune.clear_cache()
    yield
    autotune.clear_cache()


# (in_shape, (kh, kw), m, stride, padding, epilogue, groups): small but
# covering every capability axis — kernel size, stride, padding, 1x1,
# epilogue fusion, grouped/depthwise
SWEEP = [
    ((1, 8, 8, 6), (3, 3), 4, (1, 1), (1, 1), "bias_relu", 1),
    ((2, 9, 9, 5), (3, 3), 4, (2, 2), (1, 1), "none", 1),
    ((1, 6, 6, 8), (1, 1), 4, (1, 1), (0, 0), "none", 1),
    ((1, 6, 6, 8), (1, 1), 4, (1, 1), (0, 0), "bias", 1),
    ((1, 7, 7, 4), (5, 5), 3, (1, 1), (2, 2), "bias", 1),
    ((1, 8, 8, 8), (3, 3), 8, (1, 1), (1, 1), "relu", 8),     # depthwise
    ((2, 8, 8, 6), (3, 3), 4, (1, 1), (1, 1), "bias_relu", 2),
]


def _spec(geom, dtype):
    in_shape, (kh, kw), m, stride, padding, epi, groups = geom
    return cs.ConvSpec(in_shape, (kh, kw, in_shape[3] // groups, m),
                       stride, padding, dtype, epi, groups)


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
    """fp32 library reference, epilogue included."""
    y = cc.conv_lax(x.astype(jnp.float32), w.astype(jnp.float32),
                    spec.stride, spec.padding, groups=spec.groups)
    if spec.has_bias:
        y = y + b.astype(jnp.float32)
    if spec.wants_relu:
        y = jax.nn.relu(y)
    return np.asarray(y)


# ---------------------------------------------------------------------------
# (a) numerics conformance: every executor x dtype over its claimed specs

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", ex.names())
def test_executor_numerics_conform_to_declared_capabilities(rng, name,
                                                            dtype):
    exe = ex.get(name)
    ran = 0
    for geom in SWEEP:
        spec = _spec(geom, dtype)
        ok, why = exe.supports(spec)
        if not ok:
            continue
        ran += 1
        x, w, b = _operands(spec, rng)
        got = exe.execute(spec, x, w, bias=b)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), _f32_ref(spec, x, w, b),
            err_msg=f"{name} {spec.key()}", **TOLS[dtype])
    if dtype in exe.dtypes:
        assert ran > 0, (f"{name} declares dtype {dtype} but supports "
                         f"no spec in the conformance sweep")
    else:
        assert ran == 0, (f"{name} executed {dtype} specs it does not "
                          f"declare")


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("fused_add", ["add", "add_relu"])
def test_winograd_pallas_fuses_residual_add_in_kernel(rng, dtype,
                                                      fused_add):
    """winograd_pallas declares fusions() = ('add',): the residual
    operand is applied in VMEM after the inverse transform.  Forced on a
    fused-add spec, the planned execution matches conv + add (+ relu)."""
    import dataclasses
    base = _spec(SWEEP[0], dtype)                       # 3x3 s1 bias_relu
    spec = dataclasses.replace(base, epilogue="bias", fused_add=fused_add)
    exe = ex.get("winograd_pallas")
    ok, why = exe.supports(spec)
    assert ok, why
    x, w, b = _operands(spec, rng)
    ad = jnp.asarray(rng.normal(size=spec.out_shape), jnp.float32) \
        .astype(jnp.dtype(dtype))
    p = cs.plan(spec, force="winograd_pallas")
    got = np.asarray(p(x, w, b, addend=ad), np.float32)
    want = _f32_ref(spec, x, w, b) + np.asarray(ad, np.float32)
    if fused_add == "add_relu":
        want = np.maximum(want, 0.0)
    np.testing.assert_allclose(got, want, **TOLS[dtype])


@pytest.mark.parametrize("dtype", DTYPES)
def test_bf16_inputs_accumulate_fp32(rng, dtype):
    """Every executor declares fp32 accumulation; check it holds: a
    reduction long enough to drift under bf16 accumulation stays close
    to the fp32 answer."""
    spec = _spec(((1, 6, 6, 512), (1, 1), 4, (1, 1), (0, 0), "none", 1),
                 dtype)
    x, w, b = _operands(spec, rng)
    want = _f32_ref(spec, x, w, b)
    for name in ex.supporting(spec):
        exe = ex.get(name)
        assert exe.accum == "float32"
        got = np.asarray(exe.execute(spec, x, w), np.float32)
        # C=512 contraction: bf16 accumulation would drift ~0.1 rel;
        # fp32 accumulation stays within input-rounding error
        np.testing.assert_allclose(got, want, err_msg=name, **TOLS[dtype])


# ---------------------------------------------------------------------------
# (b) plan() capability honesty

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_plan_never_selects_incapable_executor(backend, dtype):
    for geom in SWEEP:
        spec = _spec(geom, dtype)
        p = cs.plan(spec, backend=backend)
        ok, why = ex.get(p.algorithm).supports(spec)
        assert ok, (f"plan chose {p.algorithm} [{p.source}] for "
                    f"{spec.key()} but it declares: {why}")


@pytest.mark.parametrize("dtype", DTYPES)
def test_forced_plans_resolve_or_refuse_loudly(dtype):
    """Forcing any registered executor either lands on a capable
    executor (forced or its declared fallback) or raises a clear error
    (grouped specs with no grouped-capable target)."""
    for geom in SWEEP:
        spec = _spec(geom, dtype)
        for name in ex.names():
            exe = ex.get(name)
            if spec.groups != 1 and not exe.supports_groups:
                with pytest.raises(ValueError, match=name):
                    cs.plan(spec, force=name)
                continue
            p = cs.plan(spec, force=name)
            assert p.source in ("forced", "fallback")
            assert ex.get(p.algorithm).supports(spec)[0]


def test_stale_measured_winner_remeasures_instead_of_short_circuiting(rng):
    """measure_algorithm must not return a persisted winner that is no
    longer registered/capable — it re-sweeps and overwrites the entry."""
    x = jnp.asarray(rng.normal(size=(1, 6, 6, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1, 1, 4, 3)), jnp.float32)
    spec = cs.ConvSpec.for_conv(x, w, 1, "same")
    autotune.record_best(spec, jax.default_backend(), "gone_executor")
    best = autotune.measure_algorithm(x, w, repeats=1,
                                      candidates=("lax", "cuconv"))
    assert best in ("lax", "cuconv")
    assert autotune.cached_best(spec) == best    # stale entry overwritten


def test_measure_skips_unknown_candidates(rng):
    """An explicit candidate list naming an unregistered plugin times
    the remaining candidates instead of crashing the sweep."""
    x = jnp.asarray(rng.normal(size=(1, 5, 5, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1, 1, 4, 2)), jnp.float32)
    best = autotune.measure_algorithm(
        x, w, repeats=1, candidates=("unregistered_plugin", "lax"))
    assert best == "lax"


def test_stale_measured_winner_never_misplans():
    """A persisted measured entry naming an executor that cannot run the
    spec (or is no longer registered) is ignored, not served."""
    spec = _spec(SWEEP[1], "float32")               # strided
    autotune.record_best(spec, "cpu", "cuconv_two_stage_pallas")  # stride-1 only
    p = cs.plan(spec, backend="cpu")
    assert p.algorithm != "cuconv_two_stage_pallas"
    assert ex.get(p.algorithm).supports(spec)[0]
    autotune.record_best(spec, "cpu", "gone_executor")
    p = cs.plan(spec, backend="cpu")
    assert p.source in ("heuristic", "cost")


def test_vmem_budget_is_an_executor_declaration():
    """The fused kernel's VMEM model lives on its registry entry; the
    budget guard is its own supports() rule."""
    fused = ex.get("cuconv_pallas")
    small = _spec(SWEEP[0], "float32")
    assert fused.vmem_bytes(small) < ex.FUSED_VMEM_BUDGET
    assert fused.supports(small)[0]
    big = cs.ConvSpec((1, 8, 2100, 1024), (3, 3, 1024, 8),
                      stride=(1, 1), padding=(1, 1))
    assert fused.vmem_bytes(big) > ex.FUSED_VMEM_BUDGET
    ok, why = fused.supports(big)
    assert not ok and "VMEM" in why
    # bf16 halves the working set estimate
    bigb = cs.ConvSpec((1, 8, 2100, 1024), (3, 3, 1024, 8),
                       stride=(1, 1), padding=(1, 1), dtype="bfloat16")
    assert fused.vmem_bytes(bigb) < fused.vmem_bytes(big)


def test_unsupported_dtype_has_clear_error():
    spec = cs.ConvSpec((1, 8, 8, 4), (3, 3, 4, 4), (1, 1), (1, 1),
                       dtype="float16")
    with pytest.raises(ValueError, match="no registered executor"):
        cs.plan(spec)
    # int8 used to be the unsupported example; the quant subsystem's
    # executor claims it now
    spec8 = cs.ConvSpec((1, 8, 8, 4), (3, 3, 4, 4), (1, 1), (1, 1),
                        dtype="int8")
    assert cs.plan(spec8).executor.name == "cuconv_int8"
    with pytest.raises(ValueError, match="dtype"):
        cs.canonical_dtype("not_a_dtype")


# ---------------------------------------------------------------------------
# registry API + third-party executors

def test_registry_lookup_and_registration_errors():
    with pytest.raises(KeyError, match="conv9000"):
        ex.get("conv9000")
    with pytest.raises(KeyError):
        ex.unregister("conv9000")
    with pytest.raises(ValueError, match="already registered"):
        ex.register(ex.LaxExecutor())
    with pytest.raises(ValueError, match="name"):
        ex.register(ex.Executor())                   # no name

    class _Inert(ex.Executor):                       # no fn, no _execute
        name = "inert"
    with pytest.raises(ValueError, match="_execute"):
        ex.register(_Inert())                        # fails at registration
    assert set(ex.registered()) == set(ex.names())
    # ALGORITHMS is the fn-backed back-compat view: fn-less builtins
    # (the int8 executor overrides execute() wholesale; the depthwise
    # kernel has only its _execute) are registered but absent from it
    assert set(ex.ALGORITHMS) <= set(ex.names())
    assert set(ex.names()) - set(ex.ALGORITHMS) == {"cuconv_int8",
                                                    "depthwise_tap"}
    assert ex.ALGORITHMS["lax"] is cc.conv_lax
    spec = cs.ConvSpec((1, 6, 6, 4), (3, 3, 4, 4), (1, 1), (1, 1))
    assert ex.capable("lax", spec)
    assert not ex.capable("conv9000", spec)       # unknown: False, no raise
    assert not ex.capable("conv1x1_pallas", spec)  # registered, incapable


def test_fn_less_executor_absent_from_algorithms_view():
    """A third-party executor that only implements _execute (fn=None)
    must not break the back-compat mapping view's iterate-then-index
    contract — it is simply absent from the view."""
    class _NoFn(ex.Executor):
        name = "no_fn_fp16"
        dtypes = ("float16",)

        def _execute(self, spec, x, w, bias, interpret):
            return cc.conv_lax(x, w, stride=spec.stride,
                               padding=spec.padding)

    ex.register(_NoFn())
    try:
        assert "no_fn_fp16" in ex.names()
        assert "no_fn_fp16" not in list(ex.ALGORITHMS)
        assert dict(ex.ALGORITHMS)                 # iterate+index never raises
        with pytest.raises(KeyError):
            ex.ALGORITHMS["no_fn_fp16"]
    finally:
        ex.unregister("no_fn_fp16")


class _ToyExecutor(ex.Executor):
    """Third-party executor: fp16-only, supports everything there,
    claims every spec with a paper-beating score."""
    name = "toy_fp16"
    dtypes = ("float16",)

    def heuristic_claim(self, spec, backend):
        return 1000, "toy region"

    def _execute(self, spec, x, w, bias, interpret):
        return cc.conv_lax(x, w, stride=spec.stride, padding=spec.padding,
                           groups=spec.groups)


def test_third_party_executor_participates_everywhere(rng):
    toy = ex.register(_ToyExecutor())
    try:
        spec = cs.ConvSpec((1, 6, 6, 4), (3, 3, 4, 4), (1, 1), (1, 1),
                           dtype="float16")
        # negotiation: only supporter AND highest claim
        p = cs.plan(spec)
        assert (p.algorithm, p.source) == ("toy_fp16", "heuristic")
        # forced resolution through the public string API
        x = jnp.asarray(rng.normal(size=spec.in_shape), jnp.float16)
        w = jnp.asarray(rng.normal(size=spec.filter_shape), jnp.float16)
        got = cc.conv2d(x, w, 1, (1, 1), algorithm="toy_fp16")
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(cc.conv_lax(x.astype(jnp.float32),
                                   w.astype(jnp.float32), 1, (1, 1))),
            rtol=2e-2, atol=2e-2)
        # measured entries naming it resolve
        autotune.record_best(spec, jax.default_backend(), "toy_fp16")
        assert cs.plan(spec).source == "measured"
    finally:
        ex.unregister("toy_fp16")
    # after unregistration the persisted winner is stale, not a crash
    with pytest.raises(ValueError, match="no registered executor"):
        cs.plan(spec)


class _QuietExecutor(ex.Executor):
    """fp16-capable executor with NO heuristic claim: the cheapest-
    supported cost tier must pick it."""
    name = "quiet_fp16"
    dtypes = ("float16",)

    def _execute(self, spec, x, w, bias, interpret):
        return cc.conv_lax(x, w, stride=spec.stride, padding=spec.padding)


def test_cost_tier_picks_cheapest_supported_when_no_claims():
    ex.register(_QuietExecutor())
    try:
        spec = cs.ConvSpec((1, 6, 6, 4), (3, 3, 4, 4), (1, 1), (1, 1),
                           dtype="float16")
        p = cs.plan(spec)
        assert (p.algorithm, p.source) == ("quiet_fp16", "cost")
        assert "cheapest" in p.reason
    finally:
        ex.unregister("quiet_fp16")


def test_explain_reports_dtype_and_provenance():
    spec = cs.ConvSpec((1, 8, 8, 6), (3, 3, 6, 4), (1, 1), (1, 1),
                       dtype="bfloat16", epilogue="bias_relu")
    p = cs.plan(spec, backend="cpu")
    txt = p.explain()
    assert "dtype=bfloat16" in txt
    assert "accum=float32" in txt
    assert f"[{p.source}]" in txt and p.algorithm in txt
