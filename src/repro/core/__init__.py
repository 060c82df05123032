from repro.core.cuconv import conv2d  # noqa: F401
from repro.core.convspec import ConvSpec, ConvPlan, plan  # noqa: F401
from repro.core.executors import (  # noqa: F401
    Executor, register, unregister)
from repro.core.graph import (  # noqa: F401
    AddOp, ConcatOp, ConvGraph, ConvOp, DenseOp, GapOp, Graph,
    GraphBuilder, GraphPlan, NormOp, PoolOp, PrecisionPolicy, plan_graph)
