"""The two configurations: published work per image, stage shapes, and
the program's graph against the plain reference at a tiny size."""
import importlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import work
from repro.core.graph import GraphBuilder
from repro.models.cnn import GraphModel

from ._tiny import CONFIGS as TINY

CONFIGS = Path(__file__).resolve().parents[1] / "configs"

def _load(name, **override):
    cfg = dict(json.loads((CONFIGS / f"{name}.json").read_text()),
               **override)
    return cfg, importlib.import_module(f"bench.configs.{name}")


def _graph(name, batch=1, **override):
    cfg, mod = _load(name, **override)
    hw = cfg["image_size"]
    b = GraphBuilder((batch, hw, hw, cfg["in_channels"]))
    mod.build(b, cfg)
    return b.graph(), cfg, mod


@pytest.mark.parametrize("name", ["resnet50", "squeezenet1_0"])
def test_work_per_image_matches_the_published_count(name):
    g, cfg, mod = _graph(name)
    macs = sum(n.macs for n in work.graph_work(g))
    assert macs == pytest.approx(cfg["macs_per_image_published"], rel=0.01)
    params = jax.eval_shape(lambda k: mod.init(k, cfg), jax.random.key(0))
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    assert n_params == pytest.approx(cfg["params_published"], rel=0.01)


def test_work_scales_with_the_batch_and_counts_bytes_once():
    one = work.graph_work(_graph("resnet50")[0])
    many = work.graph_work(_graph("resnet50", batch=32)[0])
    assert [m.macs for m in many] == [32 * o.macs for o in one]
    stem = one[0]
    # input, filter, bias, output: 4 bytes each
    assert stem.bytes == 4 * (224 * 224 * 3 + 7 * 7 * 3 * 64 + 64
                              + 112 * 112 * 64)
    assert stem.min_seconds(197e12, 819e9) == stem.bytes / 819e9


def test_resnet50_stage_shapes():
    g, _, _ = _graph("resnet50")
    assert g.shapes["pool"] == (1, 56, 56, 64)
    assert [g.shapes[f"s{s}b1add"][1:] for s in (1, 2, 3, 4)] == [
        (56, 56, 256), (28, 28, 512), (14, 14, 1024), (7, 7, 2048)]
    assert g.out_shape == (1, 1000)
    assert len(g.conv_nodes) == 53


def test_squeezenet1_0_stage_shapes():
    g, _, _ = _graph("squeezenet1_0")
    assert g.shapes["conv1"] == (1, 109, 109, 96)
    assert [g.shapes[f"pool{i}"][1:3] for i in (1, 2, 3)] == [
        (54, 54), (27, 27), (13, 13)]
    assert g.shapes["fire9cat"] == (1, 13, 13, 512)
    assert g.out_shape == (1, 1000)


@pytest.mark.parametrize("name", ["resnet50", "squeezenet1_0"])
def test_program_graph_matches_the_plain_reference(name):
    """The config's graph, planned and fused by the program, against its
    own lax reference, at a tiny size on the CPU."""
    cfg, mod = _load(name, **TINY[name])
    hw = cfg["image_size"]

    def builder(in_shape, policy):
        b = GraphBuilder(in_shape, policy)
        mod.build(b, cfg)
        return b.graph()

    model = GraphModel(builder, (hw, hw, 3))
    params = jax.jit(lambda k: mod.init(k, cfg))(jax.random.key(5))
    x = np.random.default_rng(5).standard_normal((3, hw, hw, 3),
                                                 dtype=np.float32)
    got = np.asarray(model.apply(params, x))
    ref = np.asarray(mod.reference(params, x, cfg))
    assert got.shape == (3, 10)
    np.testing.assert_allclose(got, ref, rtol=1e-4,
                               atol=1e-4 * np.abs(ref).max())
