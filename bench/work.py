"""Operations and bytes of a network's conv and dense nodes, from shapes.

The one place the benchmark counts work.  It reads the graph the
configuration writes before any fusion (``GraphPlan.base_graph``), so
the count is the same whichever executor, or fused kernel, does the
work.  Bytes count each node's input, weights, bias and output once,
at the width of the node's dtype; a conv spec that carries a fused
residual operand counts that operand once too.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

#: bytes per element of the dtypes a node may carry
_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


@dataclasses.dataclass(frozen=True)
class NodeWork:
    name: str
    kind: str            # conv | dense
    macs: int            # multiply-adds
    bytes: int

    @property
    def flops(self) -> int:
        return 2 * self.macs

    def min_seconds(self, peak_flops: float, peak_bytes: float) -> float:
        """Roofline bound: the larger of compute and memory time."""
        return max(self.flops / peak_flops, self.bytes / peak_bytes)


def conv_work(name: str, spec) -> NodeWork:
    """Work of one ``ConvSpec``: ``N*OH*OW*M*KH*KW*C/groups`` MACs."""
    n, oh, ow, m = spec.out_shape
    kh, kw, cpg, _ = spec.filter_shape
    size = _ITEMSIZE[spec.dtype]
    elems = (math.prod(spec.in_shape) + math.prod(spec.filter_shape)
             + math.prod(spec.final_shape))
    if spec.has_bias:
        elems += m
    if spec.fused_add != "none":
        elems += math.prod(spec.out_shape)
    return NodeWork(name, "conv", n * oh * ow * m * kh * kw * cpg,
                    elems * size)


def dense_work(name: str, batch: int, c_in: int, c_out: int,
               bias: bool) -> NodeWork:
    elems = batch * c_in + c_in * c_out + batch * c_out + (c_out if bias
                                                           else 0)
    return NodeWork(name, "dense", batch * c_in * c_out, elems * 4)


def graph_work(graph) -> List[NodeWork]:
    """One ``NodeWork`` per conv and dense node of a ``Graph``."""
    out = []
    for node in graph.nodes:
        if node.op == "conv":
            out.append(conv_work(node.name, node.spec))
        elif node.op == "dense":
            batch = graph.shapes[node.inputs[0]][0]
            out.append(dense_work(node.name, batch, *node.features,
                                  node.bias))
    return out
