"""The plans the benchmark's cells serve on a TPU, and their numerics.

``test_cell_plan_is_unchanged`` builds one configuration of
``bench/configs`` at 224x224, plans one bucket of its cells with
``backend="tpu"`` and an empty cache (the negotiation a chip runs; the
benchmark never tunes), and compares ``{node: "<algorithm> <launch
config>"}`` -- the format of ``bench/run.py``'s ``plans`` log line --
with ``data/served_plans.json``.  A change that moves a served node to
another executor or launch config fails here before it reaches the chip.
After a deliberate change of plans, rewrite the data file with
``PYTHONPATH=src JAX_PLATFORMS=cpu python tests/test_served_plans.py``.

``test_served_node_class_matches_lax`` runs one reduced geometry of
each class of conv node those plans hold (executor, kernel, stride,
padding, epilogue, fused add or pool, groups) through the executor the
TPU negotiation picks, in interpret mode, against a float32 ``lax``
reference.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, graph
from repro.core import convspec as cs
from repro.core.graph import GraphBuilder
from repro.models.cnn import GraphModel

ROOT = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data" / "served_plans.json"

# the buckets each configuration's cells compile (bench/traffic: bulk
# 8/32, interactive 1-8), under float32 and under the bf16 policy
# bench/run.py's Cell builds for precision="bf16"
BUCKETS = {"resnet50": (1, 2, 4, 8, 32),
           "squeezenet1_0": (8, 32),
           "convnext_tiny": (8, 32)}
POLICIES = ("float32", "bf16")
CASES = [(c, b, p) for c, bs in BUCKETS.items() for b in bs for p in POLICIES]


def _model(config: str) -> GraphModel:
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    spec = importlib.util.spec_from_file_location(
        f"served_plans_{config}", ROOT / "bench" / "configs" / f"{config}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def builder(in_shape, policy):
        b = GraphBuilder(in_shape, policy)
        mod.build(b, cfg)
        return b.graph()

    size, c = int(cfg["image_size"]), int(cfg["in_channels"])
    return GraphModel(builder, (size, size, c), name=config)


def served_graph_plan(config: str, bucket: int, policy: str):
    """The ``GraphPlan`` of one bucket program, as a chip negotiates it."""
    model = _model(config)
    return model.graph_plan((bucket,) + model.image_shape, backend="tpu",
                            precision=None if policy == "float32"
                            else policy)


def served_plans(config: str, bucket: int, policy: str) -> dict:
    """``{node: "<algorithm> <config key>"}`` of one bucket program."""
    gp = served_graph_plan(config, bucket, policy)
    return {n: f"{p.algorithm} {p.config.key() if p.config else '-'}"
            for n, p in gp.conv_plans.items()}


def _case_id(config: str, bucket: int, policy: str) -> str:
    return f"{config}-b{bucket}-{policy}"


@pytest.fixture
def empty_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    autotune.clear_cache()
    graph.clear_cache()
    yield
    autotune.clear_cache()
    graph.clear_cache()


@pytest.mark.parametrize("config,bucket,policy", CASES,
                         ids=[_case_id(*c) for c in CASES])
def test_cell_plan_is_unchanged(config, bucket, policy, empty_cache):
    want = json.loads(DATA.read_text())[_case_id(config, bucket, policy)]
    got = served_plans(config, bucket, policy)
    moved = {n: (want.get(n), got.get(n)) for n in set(want) | set(got)
             if want.get(n) != got.get(n)}
    assert not moved, f"served plans moved (recorded, now): {moved}"


@pytest.mark.parametrize("config,bucket,policy", CASES,
                         ids=[_case_id(*c) for c in CASES])
def test_served_cuconv_nodes_tile_exactly(config, bucket, policy,
                                          empty_cache):
    """Every ``cuconv_pallas`` node's blocks tile its output (``rows |
    OH``, ``tm | M``): no dead rows or lanes computed, and no XLA pad
    and slice around the kernel.  No node is excepted: every OH and M
    the cells serve has a feasible exact tiling.  ``explain()`` shows
    the share."""
    gp = served_graph_plan(config, bucket, policy)
    shares = {n: p.executor.padded_share(p.spec, p.config)
              for n, p in gp.conv_plans.items()
              if p.algorithm == "cuconv_pallas"}
    assert shares
    assert not {n: s for n, s in shares.items() if s}
    table = {line.split()[0]: line for line in gp.explain().splitlines()[1:]}
    for n, share in shares.items():
        assert f" padded={share:.3g} " in table[n], table[n]


_STEM_POOL = ("max", 3, 3, 2, 2, 1, 1)       # ResNet-50's 3x3/2 pad 1
_FIRE_POOL = ("max", 3, 3, 2, 2, 0, 0)       # SqueezeNet's 3x3/2
# class -> (executor, in_shape, (k, m), stride, padding, epilogue,
#           fused_add, fused_pool, groups): each class of conv node the
# cells' plans hold, at channels small enough for interpret mode and at
# the batch / spatial size that keeps the class's negotiation
NODE_CLASSES = {
    "1x1-add_relu": ("cuconv_pallas", (1, 8, 8, 16), (1, 32), 1, 0,
                     "bias", "add_relu", (), 1),
    "1x1-bias": ("cuconv_pallas", (2, 8, 8, 16), (1, 32), 1, 0,
                 "bias", "none", (), 1),
    "1x1-add": ("cuconv_pallas", (2, 8, 8, 32), (1, 16), 1, 0,
                "bias", "add", (), 1),
    "1x1-bias_relu": ("cuconv_pallas", (1, 8, 8, 16), (1, 16), 1, 0,
                      "bias_relu", "none", (), 1),
    "1x1-bias_gelu": ("cuconv_pallas", (2, 8, 8, 16), (1, 32), 1, 0,
                      "bias_gelu", "none", (), 1),
    "1x1s2-bias": ("cuconv_pallas", (1, 8, 8, 32), (1, 64), 2, 0,
                   "bias", "none", (), 1),
    "3x3-bias_relu": ("cuconv_pallas", (1, 8, 8, 16), (3, 16), 1, 1,
                      "bias_relu", "none", (), 1),
    "3x3s2-bias_relu": ("cuconv_pallas", (1, 9, 9, 16), (3, 16), 2, 1,
                        "bias_relu", "none", (), 1),
    "2x2s2-bias": ("cuconv_pallas", (2, 8, 8, 16), (2, 32), 2, 0,
                   "bias", "none", (), 1),
    "winograd-3x3-bias_relu": ("winograd_pallas", (2, 16, 16, 8), (3, 8),
                               1, 1, "bias_relu", "none", (), 1),
    "dw7x7-bias": ("depthwise_tap", (2, 8, 8, 16), (7, 16), 1, 3,
                   "bias", "none", (), 16),
    "stem7x7s2p3-pool": ("lax", (1, 32, 32, 3), (7, 8), 2, 3,
                         "bias_relu", "none", _STEM_POOL, 1),
    "stem7x7s2p0-pool": ("lax", (1, 33, 33, 3), (7, 8), 2, 0,
                         "bias_relu", "none", _FIRE_POOL, 1),
    "stem4x4s4-bias": ("lax", (2, 16, 16, 3), (4, 8), 4, 0,
                       "bias", "none", (), 1),
    # exact tilings of the output: rows a divisor of OH that is no
    # power of two, rows = OH, tm a multiple of 128 that is none
    "1x1-rows7": ("cuconv_pallas", (1, 28, 28, 8), (1, 16), 1, 0,
                  "bias_relu", "none", (), 1),
    "1x1-add_relu-rows7": ("cuconv_pallas", (1, 28, 28, 8), (1, 16), 1, 0,
                           "bias", "add_relu", (), 1),
    "3x3s2-rows7": ("cuconv_pallas", (1, 28, 28, 8), (3, 16), 2, 1,
                    "bias_relu", "none", (), 1),
    "1x1-rows9": ("cuconv_pallas", (1, 27, 27, 8), (1, 16), 1, 0,
                  "bias_relu", "none", (), 1),
    "1x1-rows13": ("cuconv_pallas", (2, 13, 13, 8), (1, 16), 1, 0,
                   "bias_relu", "none", (), 1),
    "1x1-tm384": ("cuconv_pallas", (2, 4, 4, 8), (1, 768), 1, 0,
                  "bias_gelu", "none", (), 1),
}
# the launch config each exact-tiling class must plan
CLASS_CONFIGS = {"1x1-rows7": {"rows": 7}, "1x1-add_relu-rows7": {"rows": 7},
                 "3x3s2-rows7": {"rows": 7}, "1x1-rows9": {"rows": 9},
                 "1x1-rows13": {"rows": 13}, "1x1-tm384": {"tm": 384}}
TOLS = {"float32": dict(rtol=3e-4, atol=3e-4),
        "bfloat16": dict(rtol=3e-2, atol=3e-2)}


def _class_spec(cls: str, dtype: str) -> cs.ConvSpec:
    (_, in_shape, (k, m), stride, pad, epilogue, fused_add, pool,
     groups) = NODE_CLASSES[cls]
    spec = cs.ConvSpec(in_shape, (k, k, in_shape[3] // groups, m),
                       (stride, stride), (pad, pad), dtype, epilogue, groups)
    return dataclasses.replace(spec, fused_add=fused_add, fused_pool=pool)


def _reference(spec, x, w, b, addend):
    """float32 ``lax`` at HIGHEST precision, then the spec's epilogue."""
    f32 = lambda a: None if a is None else jnp.asarray(a, jnp.float32)
    x, w, b, addend = f32(x), f32(w), f32(b), f32(addend)
    (ph, pw), (sh, sw) = spec.padding, spec.stride
    y = jax.lax.conv_general_dilated(
        x, w, (sh, sw), ((ph, ph), (pw, pw)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=spec.groups,
        precision=jax.lax.Precision.HIGHEST) + b
    if spec.fused_add != "none":
        y = y + addend
    if spec.wants_relu or spec.fused_add == "add_relu":
        y = jax.nn.relu(y)
    if spec.activation == "gelu":
        y = jax.nn.gelu(y, approximate=False)
    if spec.fused_pool:
        _, pkh, pkw, psh, psw, pph, ppw = spec.fused_pool
        y = jax.lax.reduce_window(
            y, -jnp.inf, jax.lax.max, (1, pkh, pkw, 1), (1, psh, psw, 1),
            ((0, 0), (pph, pph), (ppw, ppw), (0, 0)))
    return np.asarray(y)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cls", list(NODE_CLASSES))
def test_served_node_class_matches_lax(cls, dtype, empty_cache):
    spec = _class_spec(cls, dtype)
    p = cs.plan(spec, backend="tpu")
    assert p.algorithm == NODE_CLASSES[cls][0], p.explain()
    for dim, want in CLASS_CONFIGS.get(cls, {}).items():
        assert p.config[dim] == want, p.explain()
    rng = np.random.default_rng(len(cls))
    kh, kw, cpg, m = spec.filter_shape
    mk = lambda shape, scale=1.0: jnp.asarray(
        rng.standard_normal(shape) * scale, jnp.float32).astype(dtype)
    x = mk(spec.in_shape)
    w = mk(spec.filter_shape, (kh * kw * cpg) ** -0.5)
    b = mk((m,))
    addend = mk(spec.out_shape) if spec.fused_add != "none" else None
    got = p(x, w, b, addend=addend)
    want = _reference(spec, x, w, b, addend)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               **TOLS[dtype])


if __name__ == "__main__":
    DATA.parent.mkdir(exist_ok=True)
    DATA.write_text(json.dumps(
        {_case_id(*c): served_plans(*c) for c in CASES},
        indent=1, sort_keys=True) + "\n")
