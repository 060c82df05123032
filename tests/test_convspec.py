"""ConvSpec plan layer: dispatch, epilogues, strides, fallbacks, cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cuconv as cc
from repro.core import convspec as cs
from repro.core import executors as ex

TOLS = {"float32": dict(rtol=3e-4, atol=3e-4),
        "bfloat16": dict(rtol=3e-2, atol=3e-2)}


@pytest.fixture(autouse=True)
def _hermetic_autotune_cache(tmp_path, monkeypatch):
    """plan() consults the persisted measured cache; point it at an
    empty per-test dir so earlier sweeps on this machine can't leak
    into heuristic assertions."""
    from repro.core import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "autotune"))
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def _mk(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)


def _lax_ref(x, w, stride, padding, bias=None, relu=False):
    y = cc.conv_lax(x.astype(jnp.float32), w.astype(jnp.float32),
                    stride, padding)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if relu:
        y = jax.nn.relu(y)
    return y


# ---------------------------------------------------------------------------
# dispatch equivalence sweep: every executor x stride x padding x dtype x K

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("K", [1, 3, 5])
@pytest.mark.parametrize("padding", ["same", "valid", 1])
@pytest.mark.parametrize("stride", [1, 2])
def test_all_algorithms_match_lax(rng, stride, padding, K, dtype):
    x = _mk(rng, (2, 10, 10, 6), dtype)
    w = _mk(rng, (K, K, 6, 5), dtype)
    want = _lax_ref(x, w, stride, padding)
    tols = TOLS[str(jnp.dtype(dtype))]
    spec = cs.ConvSpec.for_conv(x, w, stride, padding)
    for name in ex.names():
        if not cs.supports(name, spec)[0]:
            continue       # forcing would fall back: lax==lax proves nothing
        got = cc.conv2d(x, w, stride, padding, algorithm=name)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want),
            err_msg=f"{name} stride={stride} pad={padding} K={K}", **tols)


@pytest.mark.parametrize("epilogue", ["bias", "relu", "bias_relu"])
@pytest.mark.parametrize("K", [1, 3])
def test_every_algorithm_matches_lax_for_every_epilogue(rng, K, epilogue):
    """Every algorithm x epilogue lands on relu?(conv + bias?) exactly
    (fused in-kernel on the Pallas path, XLA ops elsewhere)."""
    x = _mk(rng, (1, 8, 8, 6), jnp.float32)
    w = _mk(rng, (K, K, 6, 4), jnp.float32)
    bias = _mk(rng, (4,), jnp.float32) if "bias" in epilogue else None
    act = "relu" if "relu" in epilogue else None
    want = _lax_ref(x, w, 1, "same", bias=bias, relu=act == "relu")
    spec = cs.ConvSpec.for_conv(x, w, 1, "same", bias=bias, activation=act)
    assert spec.epilogue == epilogue
    for name in ex.names():
        if not cs.supports(name, spec)[0]:
            continue
        got = cc.conv2d(x, w, 1, "same", algorithm=name, bias=bias,
                        activation=act)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want),
            err_msg=f"{name} K={K} epilogue={epilogue}", **TOLS["float32"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("stride", [1, 2])
def test_fused_epilogue_matches_lax(rng, stride, dtype):
    """The planned bias+ReLU epilogue (fused on the Pallas path, XLA ops
    elsewhere) equals relu(conv_lax + b) for every algorithm."""
    x = _mk(rng, (1, 9, 9, 8), dtype)
    w = _mk(rng, (3, 3, 8, 4), dtype)
    b = _mk(rng, (4,), dtype)
    want = _lax_ref(x, w, stride, "same", bias=b, relu=True)
    tols = TOLS[str(jnp.dtype(dtype))]
    spec = cs.ConvSpec.for_conv(x, w, stride, "same", bias=b,
                                activation="relu")
    for name in ["auto"] + [n for n in ex.names()
                            if cs.supports(n, spec)[0]]:
        got = cc.conv2d(x, w, stride, "same", algorithm=name,
                        bias=b, activation="relu")
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want),
            err_msg=f"{name} stride={stride}", **tols)


# ---------------------------------------------------------------------------
# plan() policy

def test_auto_routes_through_plan():
    spec = cs.ConvSpec((1, 7, 7, 32), (1, 1, 32, 16))
    p = cs.plan(spec)
    assert p.source in ("heuristic", "measured")
    assert p.algorithm in ex.names()
    assert p.algorithm in p.explain() and spec.key() in p.explain()
    assert "dtype=float32" in p.explain()             # precision provenance


def test_plan_respects_vmem_budget_fallback():
    """Oversized fused working sets take the library conv (the fused
    executor's own capability declaration refuses them), at any
    stride."""
    spec = cs.ConvSpec((1, 8, 2100, 1024), (3, 3, 1024, 8),
                       stride=(1, 1), padding=(1, 1))
    assert ex.get("cuconv_pallas").vmem_bytes(spec) > ex.FUSED_VMEM_BUDGET
    p = cs.plan(spec, force="cuconv_pallas")
    assert p.algorithm == "lax"
    assert p.source == "fallback"
    assert "VMEM" in p.explain()
    sspec = cs.ConvSpec((1, 8, 4100, 1024), (3, 3, 1024, 8),
                        stride=(2, 2), padding=(1, 1))
    sp = cs.plan(sspec, force="cuconv_pallas")
    assert (sp.algorithm, sp.source) == ("lax", "fallback")


_POOL = ("max", 2, 2, 2, 2, 0, 0)
# (in_shape, stride, fused pool) of specs the fused kernel refuses once
# the VMEM budget sits just under its default geometry's working set
# (or, for the pool, under every multi-row blocking that covers it)
_REFUSED = {
    "stride1-over-vmem": ((1, 8, 8, 8), (1, 1), ()),
    "stride2-over-vmem": ((1, 9, 9, 8), (2, 2), ()),
    "pool-no-blocking": ((1, 8, 8, 8), (1, 1), _POOL),
}


@pytest.mark.parametrize("case", list(_REFUSED))
def test_forced_fused_kernel_falls_back_to_lax(rng, monkeypatch, case):
    """Forced onto a spec it refuses, cuconv_pallas takes the base
    executor's declared fallback, the library conv, and the plan still
    computes relu(conv + b) (then the pool)."""
    in_shape, stride, pool = _REFUSED[case]
    spec = cs.ConvSpec(in_shape, (3, 3, 8, 4), stride, (1, 1),
                       epilogue="bias_relu")
    spec = dataclasses.replace(spec, fused_pool=pool)
    fused = ex.get("cuconv_pallas")
    need = fused.vmem_bytes(spec)          # the default geometry's
    monkeypatch.setattr(ex, "FUSED_VMEM_BUDGET",
                        need if pool else need - 1)
    ok, why = fused.supports(spec)
    assert not ok, spec.key()
    p = cs.plan(spec, force="cuconv_pallas")
    assert (p.algorithm, p.source) == ("lax", "fallback"), p.explain()
    x = _mk(rng, in_shape, jnp.float32)
    w = _mk(rng, spec.filter_shape, jnp.float32)
    b = _mk(rng, (4,), jnp.float32)
    want = _lax_ref(x, w, stride, (1, 1), bias=b, relu=True)
    if pool:
        want = jax.lax.reduce_window(want, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    np.testing.assert_allclose(np.asarray(p(x, w, b)), np.asarray(want),
                               **TOLS["float32"])


def test_plan_fallback_is_numerically_correct(rng):
    """A fallback plan still computes the right answer."""
    x = _mk(rng, (1, 6, 300, 64), jnp.float32)
    w = _mk(rng, (3, 3, 64, 4), jnp.float32)
    spec = cs.ConvSpec.for_conv(x, w, 1, "same")
    old = ex.FUSED_VMEM_BUDGET
    try:
        ex.FUSED_VMEM_BUDGET = 1024            # force the guard to trip
        p = cs.plan(spec, force="cuconv_pallas")
        assert p.source == "fallback"
        got = p(x, w)
    finally:
        ex.FUSED_VMEM_BUDGET = old
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_lax_ref(x, w, 1, "same")),
                               rtol=3e-4, atol=3e-4)


def test_forced_unknown_algorithm_raises():
    spec = cs.ConvSpec((1, 4, 4, 2), (1, 1, 2, 2))
    with pytest.raises(KeyError):
        cs.plan(spec, force="conv9000")


def test_forced_unsupported_lands_on_documented_fallbacks():
    """Every forced-but-unsupported algorithm takes _fallback_for's
    documented stand-in."""
    spec3 = cs.ConvSpec((1, 8, 8, 4), (3, 3, 4, 4), (1, 1), (1, 1))
    p = cs.plan(spec3, force="depthwise_tap")         # needs depthwise
    assert (p.source, p.algorithm) == ("fallback", "lax")
    strided = cs.ConvSpec((1, 8, 8, 4), (3, 3, 4, 4), (2, 2), (1, 1))
    p = cs.plan(strided, force="winograd_pallas")     # 3x3 stride-1 only
    assert (p.source, p.algorithm) == ("fallback", "lax")
    one = cs.ConvSpec((1, 8, 8, 4), (1, 1, 4, 4))
    p = cs.plan(one, force="winograd_pallas")         # 3x3 only
    assert (p.source, p.algorithm) == ("fallback", "lax")


def test_normalize_pad_and_stride_validation():
    assert cs.normalize_pad("same", 3, 3) == (1, 1)
    assert cs.normalize_pad((2, 1), 3, 3) == (2, 1)
    with pytest.raises(ValueError):
        cs.normalize_pad(-1, 3, 3)
    with pytest.raises(ValueError):
        cs.normalize_pad((1, 2, 3), 3, 3)             # 3-tuple: was silent
    with pytest.raises(ValueError):
        cs.normalize_pad((-1, 0), 3, 3)
    with pytest.raises(ValueError):
        cs.normalize_stride(0)
    with pytest.raises(ValueError):
        cs.normalize_stride((1, 2, 3))


def test_spec_rejects_nonpositive_output():
    with pytest.raises(ValueError):
        cs.ConvSpec((1, 2, 2, 1), (5, 5, 1, 1))       # filter > padded input


def test_spec_direct_construction_validates_stride_and_pad():
    """Direct ConvSpec construction is as strict as the normalize_* path."""
    with pytest.raises(ValueError):
        cs.ConvSpec((1, 8, 8, 4), (3, 3, 4, 4), (0, 1), (1, 1))
    with pytest.raises(ValueError):
        cs.ConvSpec((1, 8, 8, 4), (3, 3, 4, 4), (1, 1), (-1, -1))


def test_spec_key_stable_and_epilogue_sensitive():
    a = cs.ConvSpec((1, 7, 7, 8), (3, 3, 8, 4), (2, 2), (1, 1),
                    "float32", "bias_relu")
    assert a.key() == "n1h7w7c8-k3x3m4-s2x2-p1x1-float32-bias_relu"
    b = cs.ConvSpec((1, 7, 7, 8), (3, 3, 8, 4), (2, 2), (1, 1))
    assert a.key() != b.key()
    assert a.out_shape == (1, 4, 4, 4)


def test_heuristic_regions_via_plan():
    """The paper's regions, owned by plan(): on a TPU the 1x1 / small
    region takes the fused kernel, large 3x3 stride-1 the Winograd
    kernel; off the TPU every spec takes the library conv."""
    def mk(xs, ws, s, backend):
        return cs.plan(cs.ConvSpec(xs, ws, (s, s), (ws[0] // 2,) * 2),
                       backend=backend).algorithm
    regions = {((1, 7, 7, 832), (1, 1, 832, 256), 1): "cuconv_pallas",
               ((64, 56, 56, 128), (3, 3, 128, 128), 1): "winograd_pallas",
               ((1, 7, 7, 64), (3, 3, 64, 64), 2): "cuconv_pallas"}
    for (xs, ws, s), want in regions.items():
        assert mk(xs, ws, s, "tpu") == want
        assert mk(xs, ws, s, "cpu") == "lax"


def test_tpu_backend_prefers_fused_kernel():
    spec = cs.ConvSpec((1, 7, 7, 192), (3, 3, 192, 384), (2, 2), (1, 1))
    p = cs.plan(spec, backend="tpu")
    assert p.algorithm == "cuconv_pallas"
    # 1x1, bare or with an epilogue (applied in VMEM, no extra round
    # trip), takes the fused kernel too
    one = cs.ConvSpec((1, 7, 7, 832), (1, 1, 832, 256))
    assert cs.plan(one, backend="tpu").algorithm == "cuconv_pallas"
    one_epi = cs.ConvSpec((1, 7, 7, 832), (1, 1, 832, 256),
                          epilogue="bias_relu")
    assert cs.plan(one_epi, backend="tpu").algorithm == "cuconv_pallas"


# ---------------------------------------------------------------------------
# persisted measured cache

def test_measured_cache_persists_across_reload(rng, tmp_path, monkeypatch):
    from repro.core import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_cache()
    x = _mk(rng, (1, 6, 6, 8), jnp.float32)
    w = _mk(rng, (1, 1, 8, 4), jnp.float32)
    best = autotune.measure_algorithm(x, w, repeats=1,
                                      candidates=("lax", "cuconv_pallas"))
    assert best in ("lax", "cuconv_pallas")
    assert (tmp_path / "autotune.json").exists()
    # a fresh process (simulated by dropping the in-memory mirror) reads
    # the measured winner back and plan() serves it
    autotune.clear_cache()
    spec = cs.ConvSpec.for_conv(x, w, 1, "same")
    assert autotune.cached_best(spec) == best
    p = cs.plan(spec)
    assert p.source == "measured" and p.algorithm == best


def test_measured_winner_serves_epilogue_specs(rng, tmp_path, monkeypatch):
    """A sweep measured without an epilogue must pay off for the real
    model path, whose specs carry bias_relu (cache key is
    epilogue-insensitive)."""
    from repro.core import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_cache()
    x = _mk(rng, (1, 6, 6, 8), jnp.float32)
    w = _mk(rng, (3, 3, 8, 4), jnp.float32)
    best = autotune.measure_algorithm(x, w, repeats=1,
                                      candidates=("lax", "cuconv_pallas"))
    spec = cs.ConvSpec.for_conv(x, w, 1, "same", bias=jnp.zeros((4,)),
                                activation="relu")
    p = cs.plan(spec)
    assert p.source == "measured" and p.algorithm == best


def test_measured_cache_ignored_for_other_spec(rng, tmp_path, monkeypatch):
    from repro.core import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_cache()
    spec = cs.ConvSpec((1, 5, 5, 4), (3, 3, 4, 4))
    assert autotune.cached_best(spec) is None
    assert cs.plan(spec).source == "heuristic"


def test_measure_default_candidates_include_pallas(rng, tmp_path, monkeypatch):
    """Measured mode must be able to pick the kernels this repo exists
    to showcase: the default candidate set is every registered executor
    filtered by its declared capabilities, and bias/activation ride into
    the timed executions."""
    from repro.core import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_cache()
    spec = cs.ConvSpec((1, 4, 4, 4), (1, 1, 4, 3))
    cands = set(autotune.default_candidates(spec))
    assert cands == {"lax", "cuconv_pallas"}
    strided = cs.ConvSpec((1, 8, 8, 4), (3, 3, 4, 3), (2, 2), (1, 1))
    assert "winograd_pallas" not in set(
        autotune.default_candidates(strided))
    assert "winograd_pallas" in set(autotune.default_candidates(
        cs.ConvSpec((1, 8, 8, 4), (3, 3, 4, 3), (1, 1), (1, 1))))
    # the full default sweep runs (Pallas in interpret mode here) and
    # times the fused-epilogue deployment, not the bare conv
    x = _mk(rng, (1, 4, 4, 4), jnp.float32)
    w = _mk(rng, (1, 1, 4, 3), jnp.float32)
    b = _mk(rng, (3,), jnp.float32)
    best = autotune.measure_algorithm(x, w, repeats=1, bias=b,
                                      activation="relu")
    assert best in ex.names()
