from repro.core.cuconv import (  # noqa: F401
    conv2d, cuconv_stage1, cuconv_stage2)
from repro.core.convspec import ConvSpec, ConvPlan, plan  # noqa: F401
from repro.core.executors import (  # noqa: F401
    ALGORITHMS, Executor, register, unregister)
from repro.core.graph import (  # noqa: F401
    AddOp, ConcatOp, ConvGraph, ConvOp, DenseOp, GapOp, Graph,
    GraphBuilder, GraphPlan, NormOp, PoolOp, PrecisionPolicy, plan_graph)
