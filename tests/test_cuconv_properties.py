"""Property-based tests (hypothesis) for the registered conv executors."""
import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # deterministic fallback; see _hypothesis_compat
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import convspec as cs
from repro.core import cuconv as cc
from repro.core import executors as ex

conv_shapes = st.tuples(
    st.integers(1, 3),                 # N
    st.integers(3, 14),                # H (=W)
    st.sampled_from([1, 3, 5]),        # K
    st.integers(1, 24),                # C
    st.integers(1, 16),                # M
    st.integers(1, 2),                 # stride
)


def _mk(shape_tuple, seed=0):
    N, H, K, C, M, s = shape_tuple
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(N, H, H, C)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, C, M)), jnp.float32)
    return x, w, s


@settings(max_examples=40, deadline=None)
@given(conv_shapes, st.integers(0, 2**31 - 1))
def test_all_algorithms_agree(shape_tuple, seed):
    """Every executor that can run the spec equals the library
    convolution (same padding)."""
    x, w, s = _mk(shape_tuple, seed)
    if s > 1 and x.shape[1] < w.shape[0]:
        s = 1
    want = cc.conv_lax(x, w, s, "same")
    spec = cs.ConvSpec.for_conv(x, w, s, "same")
    for name in ex.names():
        if name == "lax" or not ex.get(name).supports(spec)[0]:
            continue
        got = cc.conv2d(x, w, s, "same", algorithm=name)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4, err_msg=name)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3), st.integers(2, 10), st.integers(1, 16),
       st.integers(1, 8), st.integers(0, 2**31 - 1))
def test_1x1_is_single_gemm(N, H, C, M, seed):
    """1x1 filters: the fused kernel's one tap IS the convolution, a
    single channel GEMM (the paper's fast path)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(N, H, H, C)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1, 1, C, M)), jnp.float32)
    got = cc.conv2d(x, w, 1, "valid", algorithm="cuconv_pallas")
    want = jnp.einsum("nhwc,cm->nhwm", x, w[0, 0],
                      precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 2), st.integers(4, 10), st.sampled_from([3, 5]),
       st.integers(1, 12), st.integers(1, 8), st.integers(0, 2**31 - 1))
def test_linearity_in_filters(N, H, K, C, M, seed):
    """Convolution is linear in w: conv(x, a*w1 + w2) == a*conv(x,w1)+conv(x,w2)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(N, H, H, C)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(K, K, C, M)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(K, K, C, M)), jnp.float32)
    a = 1.7
    def conv(w):
        return cc.conv2d(x, w, 1, "same", algorithm="cuconv_pallas")
    lhs = conv(a * w1 + w2)
    rhs = a * conv(w1) + conv(w2)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=1e-3, atol=1e-3)


def test_autotune_heuristic_regions():
    """The paper's regions as the executors claim them on a TPU."""
    def pick(xs, ws, stride=1, groups=1):
        spec = cs.ConvSpec(xs, ws, (stride, stride), (ws[0] // 2,) * 2,
                           groups=groups)
        return cs.heuristic_algorithm(spec, "tpu")[0]
    # 1x1: the fused kernel (the paper's winning region)
    assert pick((1, 7, 7, 832), (1, 1, 832, 256)) == "cuconv_pallas"
    # batch-1 small spatial: the fused kernel
    assert pick((1, 7, 7, 192), (3, 3, 192, 384)) == "cuconv_pallas"
    # large 3x3: Winograd's region in the paper
    assert pick((64, 56, 56, 128), (3, 3, 128, 128)) == "winograd_pallas"
    # strided: the fused kernel, except an RGB stem -> library
    assert pick((1, 7, 7, 64), (3, 3, 64, 64), stride=2) == "cuconv_pallas"
    assert pick((8, 224, 224, 3), (7, 7, 3, 64), stride=2) == "lax"
    # depthwise: the tap kernel; other grouped convs: library
    assert pick((8, 14, 14, 64), (7, 7, 1, 64), groups=64) == \
        "depthwise_tap"
    assert pick((8, 14, 14, 64), (3, 3, 32, 64), groups=2) == "lax"


def test_measured_autotune_runs(rng):
    from repro.core.autotune import measure_algorithm
    x = jnp.asarray(rng.normal(size=(1, 7, 7, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(1, 1, 32, 16)), jnp.float32)
    best = measure_algorithm(x, w, repeats=1)
    assert best in ex.names()


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 3), st.integers(3, 14), st.integers(1, 16),
       st.integers(1, 12), st.sampled_from(["same", "valid"]),
       st.integers(0, 2**31 - 1))
def test_winograd_equals_direct(N, H, C, M, pad, seed):
    """The Winograd baseline (the paper's main competitor) == library conv."""
    from repro.core.winograd import conv_winograd
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(N, H, H, C)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, C, M)), jnp.float32)
    got = conv_winograd(x, w, 1, pad)
    want = cc.conv_lax(x, w, 1, pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_winograd_filter_transform_identity():
    """A delta filter transforms to a tensor whose A^T m A collapses back
    to the identity convolution (sanity of the transform matrices)."""
    from repro.core.winograd import conv_winograd
    w = jnp.zeros((3, 3, 1, 1)).at[1, 1, 0, 0].set(1.0)   # center tap
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 8, 8, 1)),
                    jnp.float32)
    got = conv_winograd(x, w, 1, "same")
    np.testing.assert_allclose(np.asarray(got), np.asarray(x),
                               rtol=1e-4, atol=1e-4)

