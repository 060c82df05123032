"""The depthwise tap-accumulation kernel and its executor, against the
library's grouped convolution."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import convspec as cs
from repro.core import executors as ex
from repro.kernels import ops


def _lax_depthwise(x, w, pad):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), ((pad[0], pad[0]), (pad[1], pad[1])),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1],
        precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("k", [3, 7])
@pytest.mark.parametrize("c,tc", [(24, 24), (128, 128), (256, 128)])
@pytest.mark.parametrize("nb,rows", [(1, 1), (3, 2)])
def test_kernel_matches_the_library_grouped_conv(k, c, tc, nb, rows):
    rng = np.random.default_rng(k * c + nb)
    x = jnp.asarray(rng.standard_normal((3, 10, 9, c)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, 1, c)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(c), jnp.float32)
    pad = (k // 2, k // 2)
    got = ops.depthwise_conv(x, w, pad, bias=b, nb=nb, tc=tc, rows=rows,
                             interpret=True)
    np.testing.assert_allclose(got, _lax_depthwise(x, w, pad) + b,
                               rtol=1e-5, atol=1e-4)


def test_kernel_takes_any_padding_no_bias_and_a_relu():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((5, 5, 1, 16)), jnp.float32)
    got = ops.depthwise_conv(x, w, (0, 1), interpret=True)
    assert got.shape == (2, 4, 6, 16)
    np.testing.assert_allclose(got, _lax_depthwise(x, w, (0, 1)),
                               rtol=1e-5, atol=1e-4)
    got = ops.depthwise_conv(x, w, (2, 2), relu=True, interpret=True)
    np.testing.assert_allclose(got, jnp.maximum(_lax_depthwise(x, w, (2, 2)),
                                                0), rtol=1e-5, atol=1e-4)
    with pytest.raises(ValueError, match="rows=3 must divide"):
        ops.depthwise_conv(x, w, (2, 2), rows=3, interpret=True)


def _dw(n=4, h=14, c=384, k=7, epilogue="bias", stride=(1, 1)):
    return cs.ConvSpec((n, h, h, c), (k, k, 1, c), stride, (k // 2,) * 2,
                       "float32", epilogue, c)


def test_executor_takes_only_stride_1_odd_k_depthwise():
    dw = ex.get("depthwise_tap")
    for epilogue in ("none", "bias", "relu", "bias_relu"):
        assert dw.supports(_dw(epilogue=epilogue))[0]
    refused = {
        "gelu": _dw(epilogue="bias_gelu"),
        "stride": _dw(stride=(2, 2)),
        "even": _dw(k=4),
        "grouped": cs.ConvSpec((1, 8, 8, 8), (3, 3, 2, 8), (1, 1), (1, 1),
                               groups=4),
        "dense": cs.ConvSpec((1, 8, 8, 8), (3, 3, 8, 8), (1, 1), (1, 1)),
    }
    for what, spec in refused.items():
        assert not dw.supports(spec)[0], what


@pytest.mark.parametrize("n,h,c", [(8, 56, 96), (32, 56, 96), (32, 28, 192),
                                   (32, 14, 384), (8, 7, 768), (32, 7, 768)])
def test_default_config_fits_the_budget_at_convnext_widths(n, h, c):
    dw = ex.get("depthwise_tap")
    spec = _dw(n, h, c)
    cfg = dw.default_config(spec)
    assert dw.config_supports(spec, cfg)[0]
    assert dw.vmem_bytes(spec, cfg) <= ex.FUSED_VMEM_BUDGET


@pytest.mark.parametrize("h,c,cfg", [
    (56, 96, "nb=1,rows=2,tc=96"),
    (28, 192, "nb=2,rows=2,tc=192"),
    (14, 384, "nb=16,rows=7,tc=128"),
    (7, 768, "nb=32,rows=7,tc=128"),
])
def test_tpu_plans_take_the_kernel_where_the_chip_timed_it_faster(h, c, cfg):
    """ConvNeXt-T's stage shapes at bucket 32: the kernel, which beat
    the library conv at each inside the served program on a v5e, with
    the launch config timed fastest."""
    p = cs.plan(_dw(32, h, c), backend="tpu")
    assert (p.algorithm, p.config.key()) == ("depthwise_tap", cfg)


def test_planned_depthwise_runs_as_the_library_conv_does():
    """Off the TPU the library conv keeps depthwise specs; forced, the
    kernel gives the library's answer through a ConvPlan."""
    spec = _dw(n=2, h=9, c=24)
    assert cs.plan(spec).algorithm == "lax"
    p = cs.plan(spec, force="depthwise_tap")
    assert (p.algorithm, p.source) == ("depthwise_tap", "forced")
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal(spec.in_shape), jnp.float32)
    w = jnp.asarray(rng.standard_normal(spec.filter_shape), jnp.float32)
    b = jnp.asarray(rng.standard_normal(24), jnp.float32)
    np.testing.assert_allclose(p(x, w, b), _lax_depthwise(x, w, (3, 3)) + b,
                               rtol=1e-5, atol=1e-4)
