"""Names a device trace attributes work by: every graph node's ops carry
the node's name in their ``op_name`` metadata, and every bucket program
is a module named after its bucket."""
import re

import jax
import numpy as np
import pytest

from repro.core.graph import GraphBuilder
from repro.models.cnn import GraphModel, fire_like, resnet_like
from repro.serve.cnn import BucketPrograms


@pytest.mark.parametrize("make,fuse", [
    (resnet_like, True), (resnet_like, False), (fire_like, True)],
    ids=["resnet_fused", "resnet_unfused", "fire"])
def test_compiled_bucket_program_names_its_bucket_and_every_node(make, fuse):
    model = make(num_classes=4)
    params = model.init(jax.random.PRNGKey(0))
    progs = BucketPrograms(model, params, (16, 16, 3), buckets=(2,),
                           fuse=fuse)
    x = progs.put(np.zeros((2, 16, 16, 3), np.float32))
    hlo = progs.fn(2).lower(progs.params, x).compile().as_text()
    assert hlo.startswith("HloModule jit_serve_b2,")
    scopes = {part for name in re.findall(r'op_name="([^"]*)"', hlo)
              for part in name.split("/")}
    nodes = [n.name for n in progs.graph_plan(2).graph.nodes]
    assert len(nodes) > 4
    assert not [n for n in nodes if n not in scopes]


def _tiny_convnext(in_shape, policy):
    """A stem, one ConvNeXt block and the head, at tiny widths."""
    b = GraphBuilder(in_shape, policy)
    y = b.conv("stem", "input", 4, 8, stride=4, padding=0, epilogue="bias")
    y = b.norm("stemnorm", y)
    z = b.conv("b1dw", y, 7, 8, padding=3, epilogue="bias", groups=8)
    z = b.norm("b1norm", z)
    z = b.conv("b1pw1", z, 1, 32, epilogue="bias_gelu")
    z = b.conv("b1pw2", z, 1, 8, epilogue="bias")
    y = b.add("b1add", (y, z))
    y = b.norm("headnorm", b.gap("gap", y))
    b.dense("head", y, 4)
    return b.graph()


def test_compiled_convnext_program_names_its_norm_and_depthwise_nodes():
    """Planned for the TPU (the kernels run in interpret mode here), the
    bucket program names every norm node and the depthwise node in its
    ops, and the depthwise kernel under its own name."""
    model = GraphModel(_tiny_convnext, (16, 16, 3))
    progs = BucketPrograms(model, model.init(jax.random.PRNGKey(0)),
                           (16, 16, 3), buckets=(2,), backend="tpu")
    gp = progs.graph_plan(2)
    assert gp.conv_plans["b1dw"].algorithm == "depthwise_tap"
    assert gp.conv_plans["b1pw1"].spec.epilogue == "bias_gelu"
    x = progs.put(np.zeros((2, 16, 16, 3), np.float32))
    hlo = progs.fn(2).lower(progs.params, x).compile().as_text()
    paths = [name.split("/") for name in re.findall(r'op_name="([^"]*)"',
                                                    hlo)]
    scopes = {part for p in paths for part in p}
    norms = [n.name for n in gp.graph.nodes if n.op == "norm"]
    assert norms == ["stemnorm", "b1norm", "headnorm"]
    assert not [n for n in norms + ["b1dw", "b1pw1"] if n not in scopes]
    assert any("b1dw" in p and "depthwise_tap" in p for p in paths)
