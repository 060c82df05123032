"""Names a device trace attributes work by: every graph node's ops carry
the node's name in their ``op_name`` metadata, and every bucket program
is a module named after its bucket."""
import re

import jax
import numpy as np
import pytest

from repro.models.cnn import fire_like, resnet_like
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
