"""Grouped/depthwise convolutions: ConvSpec.groups through the planner,
property-tested against ``lax.conv_general_dilated(feature_group_count)``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # deterministic fallback; see _hypothesis_compat
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import convspec as cs
from repro.core import cuconv as cc
from repro.core import executors as ex


@pytest.fixture(autouse=True)
def _hermetic_autotune_cache(tmp_path, monkeypatch):
    from repro.core import autotune
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "cache"))
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def _lax_grouped(x, w, stride, padding, groups):
    kh, kw = w.shape[0], w.shape[1]
    ph, pw = cs.normalize_pad(padding, kh, kw)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=cs.normalize_stride(stride),
        padding=((ph, ph), (pw, pw)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


grouped_shapes = st.tuples(
    st.integers(1, 2),                 # N
    st.integers(5, 12),                # H (=W)
    st.sampled_from([1, 3, 5]),        # K
    st.integers(1, 5),                 # C per group
    st.sampled_from([1, 2, 4]),        # groups
    st.integers(1, 3),                 # M per group
    st.integers(1, 2),                 # stride
)


@settings(max_examples=40, deadline=None)
@given(grouped_shapes, st.sampled_from(["same", "valid", 1]),
       st.integers(0, 2**31 - 1))
def test_grouped_conv2d_matches_feature_group_count(shape_tuple, padding,
                                                    seed):
    """conv2d(..., groups=g) == the library grouped conv, across
    stride / padding / groups (depthwise included via C_per_group=1)."""
    N, H, K, cpg, groups, mpg, s = shape_tuple
    if padding == "valid" and H < K:
        s, padding = 1, "same"
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(N, H, H, cpg * groups)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, cpg, groups * mpg)), jnp.float32)
    got = cc.conv2d(x, w, s, padding, groups=groups)
    want = _lax_grouped(x, w, s, padding, groups)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


@settings(max_examples=15, deadline=None)
@given(grouped_shapes, st.integers(0, 2**31 - 1))
def test_grouped_epilogue_matches_reference(shape_tuple, seed):
    """bias+ReLU rides a grouped conv exactly like an ungrouped one."""
    N, H, K, cpg, groups, mpg, s = shape_tuple
    rng = np.random.default_rng(seed)
    m = groups * mpg
    x = jnp.asarray(rng.normal(size=(N, H, H, cpg * groups)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, cpg, m)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(m,)), jnp.float32)
    got = cc.conv2d(x, w, s, "same", groups=groups, bias=b,
                    activation="relu")
    want = jax.nn.relu(_lax_grouped(x, w, s, "same", groups) + b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# planner policy for grouped specs

def _dw_spec(c=8, h=8, k=3):
    return cs.ConvSpec((1, h, h, c), (k, k, 1, c), (1, 1),
                       ((k - 1) // 2,) * 2, "float32", "none", c)


def test_grouped_spec_validation():
    with pytest.raises(ValueError, match="groups"):
        cs.ConvSpec((1, 8, 8, 8), (3, 3, 1, 8), groups=0)
    with pytest.raises(ValueError, match="channel mismatch"):
        cs.ConvSpec((1, 8, 8, 8), (3, 3, 2, 8), groups=8)
    with pytest.raises(ValueError, match="divisible"):
        cs.ConvSpec((1, 8, 8, 8), (3, 3, 2, 6), groups=4)
    # ungrouped key shape is unchanged (old persisted entries stay valid)
    assert "-g" not in cs.ConvSpec((1, 8, 8, 4), (3, 3, 4, 4),
                                   padding=(1, 1)).key()
    assert _dw_spec().key().endswith("-g8")


def test_grouped_plan_routes_to_library_conv():
    spec = _dw_spec()
    p = cs.plan(spec)
    assert (p.algorithm, p.source) == ("lax", "heuristic")
    assert "feature_group_count" in p.reason
    for name in ex.names():
        ok, why = cs.supports(name, spec)
        assert ok == (name in ("lax", "depthwise_tap")), name
    # a grouped spec that is not depthwise has the library conv alone
    grouped = cs.ConvSpec((1, 8, 8, 8), (3, 3, 2, 8), (1, 1), (1, 1),
                          groups=4)
    for name in ex.names():
        assert cs.supports(name, grouped)[0] == (name == "lax"), name


def test_forcing_ungrouped_executor_on_grouped_spec_raises():
    """Forcing an executor that cannot run grouped specs is a loud,
    named error at plan time — not a silent fallback to a different
    algorithm than the caller demanded, and not a failure deep inside
    the kernel."""
    spec = _dw_spec()
    with pytest.raises(ValueError) as err:
        cs.plan(spec, force="cuconv_pallas")
    msg = str(err.value)
    assert "cuconv_pallas" in msg             # names the executor
    assert spec.key() in msg                  # names the spec
    assert "groups" in msg
    with pytest.raises(ValueError, match="winograd"):
        cs.plan(spec, force="winograd")
    # a grouped-capable executor still forces cleanly
    fp = cs.plan(spec, force="lax")
    assert (fp.algorithm, fp.source) == ("lax", "forced")


def test_grouped_measure_and_heuristic_on_tpu_backend(rng):
    """Measured mode times the library conv and the depthwise kernel for
    a depthwise spec, and records the faster."""
    from repro.core import autotune
    spec = _dw_spec()
    assert tuple(autotune.default_candidates(spec)) == ("lax",
                                                        "depthwise_tap")
    assert cs.plan(spec, backend="tpu").algorithm == "depthwise_tap"
    x = jnp.asarray(rng.normal(size=spec.in_shape), jnp.float32)
    w = jnp.asarray(rng.normal(size=spec.filter_shape), jnp.float32)
    best = autotune.measure_algorithm(x, w, repeats=1, groups=spec.groups)
    assert best in ("lax", "depthwise_tap")
    assert autotune.cached_best(spec) == best


@pytest.mark.parametrize("hw,k,m,c,groups", [
    (28, 3, 128, 128, 128),            # MobileNet v1 depthwise stage
    (14, 3, 256, 256, 256),
])
def test_real_mobilenet_depthwise_configs_plan_and_run(rng, hw, k, m, c,
                                                       groups):
    from repro.configs.cnn_paper import MOBILENET_DW
    assert (hw, k, m, c, groups) in MOBILENET_DW
    x = jnp.asarray(rng.normal(size=(1, hw, hw, c)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, k, c // groups, m)), jnp.float32)
    spec = cs.ConvSpec.for_conv(x, w, 1, "same", groups=groups)
    p = cs.plan(spec)
    assert p.algorithm == "lax"
    got = p(x, w)
    want = _lax_grouped(x, w, 1, "same", groups)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_unknown_activation_raises():
    """for_conv must not silently drop unknown activations (an old
    behaviour planned epilogue 'none' for any unknown one)."""
    x = jnp.zeros((1, 8, 8, 4), jnp.float32)
    w = jnp.zeros((3, 3, 4, 4), jnp.float32)
    with pytest.raises(ValueError, match="tanh"):
        cs.ConvSpec.for_conv(x, w, 1, "same", activation="tanh")
    with pytest.raises(ValueError, match="activation"):
        cc.conv2d(x, w, 1, "same", bias=jnp.zeros((4,)),
                  activation="swish")
    # the accepted spellings still work
    assert cs.ConvSpec.for_conv(x, w, activation="relu").epilogue == "relu"
    assert cs.ConvSpec.for_conv(x, w, activation="none").epilogue == "none"
    assert cs.ConvSpec.for_conv(x, w, activation=None).epilogue == "none"
    assert cs.ConvSpec.for_conv(x, w, activation="gelu").epilogue == "gelu"
    assert cs.ConvSpec.for_conv(x, w, bias=jnp.zeros((4,)),
                                activation="gelu").epilogue == "bias_gelu"
