"""The LayerNorm graph node and the exact-GELU conv epilogue: shape
inference, signatures, the in-kernel erf, the fused kernel's GELU
branch, and which executors may take a GELU spec."""
import importlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import convspec as cs
from repro.core import cuconv as cc
from repro.core import executors as ex
from repro.core.graph import GraphBuilder, NormOp, plan_graph
from repro.kernels import cuconv_fused, ops

BENCH_CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"


def _gelu(y):
    return jax.nn.gelu(y, approximate=False)


def test_norm_node_keeps_rank_4_and_rank_2_shapes_and_names_its_eps():
    b = GraphBuilder((2, 6, 6, 3))
    y = b.conv("c", "input", 3, 8, epilogue="bias")
    y = b.norm("n4", y, eps=1e-5)
    y = b.gap("gap", y)
    y = b.norm("n2", y)
    g = b.graph()
    assert g.shapes["n4"] == (2, 6, 6, 8)
    assert g.shapes["n2"] == (2, 8)
    assert g.node("n4").descriptor() == "norm:n4<c>:eps=1e-05"
    assert g.node("n2").eps == 1e-6
    other = GraphBuilder((2, 6, 6, 3))
    other.norm("n4", other.conv("c", "input", 3, 8, epilogue="bias"),
               eps=1e-6)
    assert other.graph().signature() != g.signature()
    with pytest.raises(ValueError, match="NHWC or"):
        NormOp("n", ("x",)).infer_shape([(2, 6, 8)])
    with pytest.raises(ValueError, match="eps"):
        NormOp("n", ("x",), eps=0.0)


def test_norm_node_runs_layer_norm_over_channels():
    b = GraphBuilder((2, 5, 5, 4))
    b.norm("n", "input", eps=1e-6)
    gp = plan_graph(b.graph(), use_cache=False)
    x = np.random.default_rng(0).standard_normal((2, 5, 5, 4),
                                                 dtype=np.float32)
    g = np.linspace(0.5, 1.5, 4, dtype=np.float32)
    beta = np.linspace(-0.1, 0.1, 4, dtype=np.float32)
    got = np.asarray(gp.run(x, {"n": {"g": g, "b": beta}}))
    mu = x.mean(-1, keepdims=True)
    want = (x - mu) / np.sqrt(x.var(-1, keepdims=True) + 1e-6) * g + beta
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="params missing g for norm"):
        gp.run(x, {"n": {"b": beta}})


@pytest.mark.parametrize("name,batch,signature", [
    ("resnet50", 1, "65ef585f518ccdfe"),
    ("resnet50", 32, "da2ff65fa5647b82"),
    ("squeezenet1_0", 1, "968156d06a5f2ff3"),
    ("squeezenet1_0", 32, "6b363289f8cd7406"),
])
def test_graphs_without_norm_nodes_keep_their_signatures(name, batch,
                                                         signature):
    """The benchmark's networks keep the plan-cache keys they had before
    the norm node and the GELU epilogues existed."""
    cfg = json.loads((BENCH_CONFIGS / f"{name}.json").read_text())
    mod = importlib.import_module(f"bench.configs.{name}")
    b = GraphBuilder((batch, 224, 224, 3))
    mod.build(b, cfg)
    assert b.graph().signature() == signature


def test_pallas_erf_is_within_1e_6_of_lax_erf():
    x = jnp.linspace(-8.0, 8.0, 512 * 128, dtype=jnp.float32)
    x = x.reshape(512, 128)

    def kernel(x_ref, o_ref):
        o_ref[...] = cuconv_fused.erf(x_ref[...])
    got = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        x.shape, x.dtype), interpret=True)(x)
    assert float(jnp.abs(got - jax.lax.erf(x)).max()) <= 1e-6
    y = jnp.linspace(-6.0, 6.0, 1001, dtype=jnp.float32)
    np.testing.assert_allclose(cuconv_fused.gelu(y), _gelu(y), atol=1e-6)


@pytest.mark.parametrize("stride,k", [(1, 1), (1, 3), (2, 3)])
def test_fused_kernel_gelu_with_bias_and_residual_add(stride, k):
    rng = np.random.default_rng(stride * 10 + k)
    x = jnp.asarray(rng.standard_normal((2, 9, 9, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, 6, 12)) / k, jnp.float32)
    b = jnp.asarray(rng.standard_normal(12), jnp.float32)
    pad = (k // 2, k // 2)
    conv = jax.lax.conv_general_dilated(
        x, w, (stride, stride), (pad, pad),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    a = jnp.asarray(rng.standard_normal(conv.shape), jnp.float32)
    got = ops.cuconv_fused(x, w, pad, stride=stride, bias=b,
                           activation="gelu", addend=a, rows=2,
                           interpret=True)
    np.testing.assert_allclose(got, _gelu(conv + b + a), rtol=1e-4,
                               atol=1e-4)


def _gelu_spec(epilogue="bias_gelu"):
    return cs.ConvSpec((2, 8, 8, 6), (3, 3, 6, 8), (1, 1), (1, 1),
                       "float32", epilogue)


@pytest.mark.parametrize("epilogue", ["gelu", "bias_gelu"])
def test_every_executor_without_gelu_refuses_it(epilogue):
    spec = _gelu_spec(epilogue)
    assert spec.activation == "gelu"
    assert spec.has_bias == (epilogue == "bias_gelu")
    for name, exe in ex.registered().items():
        ok, why = exe.supports(spec)
        if epilogue not in exe.epilogues:
            assert not ok and "epilogue" in why, name
    assert not ex.get("winograd_pallas").supports(spec)[0]
    assert ex.get("cuconv_pallas").supports(spec)[0]
    assert ex.get("lax").supports(spec)[0]
    # the planner never lands on an executor that would drop the GELU
    for backend in ("cpu", "tpu"):
        algo = cs.plan(spec, backend=backend).algorithm
        assert epilogue in ex.get(algo).epilogues
    with pytest.raises(ValueError, match="activation moves AFTER"):
        cs.ConvSpec((2, 8, 8, 6), (1, 1, 6, 6), epilogue="bias_gelu",
                    fused_add="add")


@pytest.mark.parametrize("algorithm", ["lax", "cuconv_pallas"])
def test_gelu_epilogue_matches_the_reference_on_each_executor(algorithm):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 6, 8)) / 3, jnp.float32)
    b = jnp.asarray(rng.standard_normal(8), jnp.float32)
    got = cc.conv2d(x, w, 1, "same", algorithm, bias=b, activation="gelu")
    want = _gelu(jax.lax.conv_general_dilated(
        x, w, (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST) + b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
