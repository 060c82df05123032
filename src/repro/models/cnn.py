"""CNN inference graphs over the cuConv core (the paper's own domain).

The paper evaluates convolution configurations drawn from five real
CNNs (AlexNet, GoogLeNet, ResNet, SqueezeNet, VGG); this module builds
runnable networks of that shape whose ENTIRE forward pass — convs,
pooling, residual adds, fire-module concats, depthwise stages, GAP +
dense head — is one typed-IR program planned through the graph layer
(core/graph.py).  A model resolves a ``GraphPlan`` per input geometry
exactly once (memoized, and persisted across processes via the
graph-level cache) and every ``apply`` executes that pre-resolved
program: no per-call-site re-planning anywhere, observable via
``convspec.PLAN_STATS``.

``GraphModel`` is the generic carrier (name-keyed params mirroring the
IR's node names); ``SimpleCNN`` keeps the chain-era list-of-layers
interface on top of it; ``resnet_like``/``mobilenet_like``/``fire_like``
exercise the operator kinds the paper's networks need.  ``conv_block``
remains as the eager one-off path for standalone layer experiments.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cuconv
from repro.core.graph import (ConvOp, DenseOp, Graph, GraphBuilder,
                              GraphPlan, NormOp, PrecisionPolicy, plan_graph)


def init_conv(key, kh, kw, c_in, c_out, dtype=jnp.float32):
    scale = 1.0 / np.sqrt(kh * kw * c_in)
    k1, k2 = jax.random.split(key)
    return {
        "w": jax.random.normal(k1, (kh, kw, c_in, c_out), dtype) * scale,
        "b": jnp.zeros((c_out,), dtype),
    }


def conv_block(p, x, stride=1, padding="same", algorithm="auto"):
    # eager per-call path: bias+ReLU ride the conv as a planned epilogue
    # (fused in VMEM on the Pallas path, plain XLA ops elsewhere).  Model
    # inference goes through the pre-resolved GraphPlan instead.
    return cuconv.conv2d(x, p["w"], stride, padding, algorithm,
                         bias=p["b"], activation="relu")


def maxpool(x, k=2, s=2):
    # eager standalone pooling; the IR's PoolOp nodes run the same
    # executor inside planned programs
    from repro.kernels import ops
    return ops.pool2d(x, "max", (k, k), (s, s))


# ---------------------------------------------------------------------------
# generic IR-backed model

class GraphModel:
    """A CNN whose whole forward pass is one planned Graph program.

    ``builder(in_shape, precision) -> Graph`` defines the architecture
    for one input geometry (``precision`` is a ``PrecisionPolicy`` —
    ``GraphBuilder`` accepts it wherever a dtype string went); params
    are a name-keyed dict mirroring the IR (``{node_name: {"w": ...,
    "b": ...}}`` for conv and dense nodes, ``{"g": ..., "b": ...}`` for
    norm nodes).  Param shapes are
    geometry-independent (GAP decouples the head from the spatial
    extent), so ``init`` builds the graph once at the model's canonical
    ``image_shape``.  Master params are always fp32; a bf16 policy casts
    at the planned conv nodes (fp32 accumulation per the executors'
    declarations).
    """

    def __init__(self, builder: Callable[[Tuple[int, ...], str], Graph],
                 image_shape: Tuple[int, int, int], name: str = "graph_cnn",
                 precision=None):
        self.builder = builder
        self.image_shape = tuple(map(int, image_shape))     # (H, W, C)
        self.name = name
        # model-level default policy; None defers to the input dtype
        self.precision = (None if precision is None
                          else PrecisionPolicy.of(precision))
        self._plan_cache: Dict[tuple, GraphPlan] = {}

    def _policy(self, precision=None, dtype=None) -> PrecisionPolicy:
        """Effective policy: per-call precision > model default > the
        legacy per-call dtype string (derived from the input array)."""
        if precision is not None:
            return PrecisionPolicy.of(precision)
        if self.precision is not None:
            return self.precision
        return PrecisionPolicy.of(dtype)

    # -- graph planning --------------------------------------------------
    def graph(self, in_shape, dtype: str = "float32",
              precision=None) -> Graph:
        """The whole-network IR for one input geometry."""
        pol = self._policy(precision, dtype)
        return self.builder(tuple(map(int, in_shape)), pol)

    def graph_plan(self, in_shape, *, backend: Optional[str] = None,
                   force: Optional[str] = None, dtype: str = "float32",
                   precision=None, fuse: bool = True) -> GraphPlan:
        """The whole-network plan for one input geometry, resolved once
        per (geometry, backend, force, precision, fuse) and memoized on
        the model.  ``fuse=False`` serves the unfused program (the
        cross-layer fusion pass is on by default).

        A ``quant.QuantPolicy`` rides the same ``precision=`` parameter
        (it IS a PrecisionPolicy): the int8 quantize pass runs inside
        ``plan_graph``, and the memo key carries the calibration
        generation so a recalibration re-quantizes instead of serving a
        plan built on stale scales."""
        backend = backend or jax.default_backend()
        pol = self._policy(precision, dtype)
        quant = pol.quantizer()
        key = (tuple(map(int, in_shape)), backend, force, pol.key(), fuse)
        if quant is not None:
            from repro.quant import calibrate
            key = key + (calibrate.generation(),)
        gp = self._plan_cache.get(key)
        if gp is None:
            gp = plan_graph(self.graph(in_shape, precision=pol),
                            backend=backend, force=force, fuse=fuse,
                            quant=quant)
            self._plan_cache[key] = gp
        return gp

    # -- params ----------------------------------------------------------
    def init(self, key):
        """Name-keyed params for every conv/dense/norm node of the graph."""
        graph = self.graph((1,) + self.image_shape)
        needy = [n for n in graph.nodes
                 if isinstance(n, (ConvOp, DenseOp, NormOp))]
        keys = jax.random.split(key, max(len(needy), 1))
        params: Dict[str, Dict] = {}
        for k, node in zip(keys, needy):
            if isinstance(node, ConvOp):
                kh, kw, cpg, m = node.spec.filter_shape
                p = init_conv(k, kh, kw, cpg, m)
                if not node.spec.has_bias:
                    del p["b"]
            elif isinstance(node, NormOp):
                c = graph.shapes[node.name][-1]
                p = {"g": jnp.ones((c,), jnp.float32),
                     "b": jnp.zeros((c,), jnp.float32)}
            else:
                c_in, c_out = node.features
                p = {"w": jax.random.normal(k, (c_in, c_out), jnp.float32)
                     / np.sqrt(c_in)}
                if node.bias:
                    p["b"] = jnp.zeros((c_out,), jnp.float32)
            params[node.name] = p
        return params

    # -- execution -------------------------------------------------------
    def apply(self, params, x, algorithm="auto",
              graph_plan: Optional[GraphPlan] = None, precision=None):
        """Run the planned program.  ``algorithm`` other than "auto"
        forces that registered executor for every conv node, subject to
        each executor's declared capabilities — on a network with
        grouped/depthwise nodes, forcing an executor that cannot run
        them raises (force "lax" or use "auto"); ``precision`` overrides
        the model's PrecisionPolicy for this call; passing ``graph_plan``
        skips the memo entirely (serving engines hold their own
        per-bucket plans)."""
        gp = graph_plan or self.graph_plan(
            x.shape, force=None if algorithm == "auto" else algorithm,
            dtype=str(x.dtype), precision=precision)
        return gp.run(x, params)


# ---------------------------------------------------------------------------
# chain-era interface, now lowered onto the IR

class SimpleCNN(GraphModel):
    """Sequential conv stack + GAP head; spec: [(kh, kw, c_out, stride), ...].

    The WHOLE forward pass (conv chain, GAP, head) is one plannable
    program (planning/memoization inherited from GraphModel).  Params
    keep the chain-era layout (``{"convs": [...], "head": matrix}``)
    and are mapped onto the IR's node names inside ``apply``.
    """

    def __init__(self, spec: Sequence[Tuple[int, int, int, int]],
                 num_classes: int = 10, in_channels: int = 3):
        self.spec, self.num_classes, self.in_channels = (
            tuple(spec), num_classes, in_channels)
        super().__init__(self._build, (32, 32, in_channels),
                         name="simple_cnn")

    def _build(self, in_shape, dtype: str) -> Graph:
        """The whole-network IR for one input geometry: the conv chain
        (bias_relu epilogue per block, node names matching what
        ``ConvGraph.chain(...).to_ir()`` produces) plus GAP + dense head."""
        b = GraphBuilder(in_shape, dtype)
        y = "input"
        for i, (kh, kw, co, s) in enumerate(self.spec):
            y = b.conv(f"conv{i}", y, (kh, kw), co, stride=s)
        y = b.gap("gap", y)
        b.dense("head", y, self.num_classes, bias=False)
        return b.graph()

    def init(self, key):
        params: List = []
        c = self.in_channels
        keys = jax.random.split(key, len(self.spec) + 1)
        for i, (kh, kw, co, s) in enumerate(self.spec):
            params.append(init_conv(keys[i], kh, kw, c, co))
            c = co
        head = (jax.random.normal(keys[-1], (c, self.num_classes), jnp.float32)
                / np.sqrt(c))
        return {"convs": params, "head": head}

    def apply(self, params, x, algorithm="auto",
              graph_plan: Optional[GraphPlan] = None, precision=None):
        """Run the planned program (see GraphModel.apply)."""
        named = {f"conv{i}": p for i, p in enumerate(params["convs"])}
        named["head"] = {"w": params["head"]}
        return super().apply(named, x, algorithm, graph_plan, precision)


# ---------------------------------------------------------------------------
# model builders: the operator kinds the paper's networks need

def squeezenet_like():
    """Small SqueezeNet-flavoured stack (1x1-heavy: cuConv's best region)."""
    return SimpleCNN([
        (3, 3, 64, 2),
        (1, 1, 16, 1), (1, 1, 64, 1), (3, 3, 64, 1),
        (1, 1, 32, 1), (1, 1, 128, 1), (3, 3, 128, 1),
        (1, 1, 48, 1), (1, 1, 192, 1), (3, 3, 192, 1),
    ])


def tiny_cnn(num_classes: int = 3):
    """The deliberately tiny two-conv stack the multi-device smoke
    deployment serves (configs/serve.py DIST_SMOKE): per-image compute
    small enough that CPU-CI scaling runs are dominated by the fixed
    per-batch scheduling cost the device-count-aware buckets amortize —
    the same model tests/benchmarks share so the scaling and bitwise
    records describe one named deployment."""
    return SimpleCNN([(3, 3, 6, 2), (1, 1, 4, 1)],
                     num_classes=num_classes)


def resnet_like(num_classes: int = 10, image_shape=(32, 32, 3),
                precision=None):
    """Small ResNet-flavoured network: stem, maxpool, an identity
    residual block, a downsampling residual block with 1x1 projection,
    GAP + dense head — all inside ONE planned program.

    Each residual branch's last conv plans epilogue ``bias`` (no ReLU);
    the post-add ReLU lives on the ``add`` node, as in the real network.
    """
    def build(in_shape, dtype):
        b = GraphBuilder(in_shape, dtype)
        y = b.conv("stem", "input", 3, 16)
        y = b.pool("pool", y, kind="max", window=2)
        # identity block
        z = b.conv("b1c1", y, 3, 16)
        z = b.conv("b1c2", z, 3, 16, epilogue="bias")
        y = b.add("b1add", (y, z), activation="relu")
        # downsampling block with projection shortcut
        z = b.conv("b2c1", y, 3, 32, stride=2)
        z = b.conv("b2c2", z, 3, 32, epilogue="bias")
        p = b.conv("b2proj", y, 1, 32, stride=2, epilogue="bias")
        y = b.add("b2add", (p, z), activation="relu")
        y = b.gap("gap", y)
        b.dense("head", y, num_classes)
        return b.graph()
    return GraphModel(build, image_shape, name="resnet_like",
                      precision=precision)


def mobilenet_like(num_classes: int = 10, image_shape=(32, 32, 3),
                   precision=None):
    """Small MobileNet-flavoured network: strided stem, two depthwise-
    separable stages (3x3 depthwise conv with groups=C, then 1x1
    pointwise), GAP + dense head — all inside ONE planned program."""
    def build(in_shape, dtype):
        b = GraphBuilder(in_shape, dtype)
        y = b.conv("stem", "input", 3, 16, stride=2)
        y = b.conv("dw1", y, 3, 16, groups=16)
        y = b.conv("pw1", y, 1, 32)
        y = b.conv("dw2", y, 3, 32, stride=2, groups=32)
        y = b.conv("pw2", y, 1, 64)
        y = b.gap("gap", y)
        b.dense("head", y, num_classes)
        return b.graph()
    return GraphModel(build, image_shape, name="mobilenet_like",
                      precision=precision)


def fire_like(num_classes: int = 10, image_shape=(32, 32, 3),
              precision=None):
    """SqueezeNet fire module done properly: squeeze 1x1 feeding 1x1 and
    3x3 expand branches whose outputs CONCAT on the channel axis —
    planned as one program (the chain API could not express this)."""
    def build(in_shape, dtype):
        b = GraphBuilder(in_shape, dtype)
        y = b.conv("stem", "input", 3, 16, stride=2)
        s = b.conv("squeeze", y, 1, 8)
        e1 = b.conv("expand1", s, 1, 16)
        e3 = b.conv("expand3", s, 3, 16)
        y = b.concat("cat", (e1, e3))
        y = b.pool("pool", y, kind="avg", window=2)
        y = b.gap("gap", y)
        b.dense("head", y, num_classes)
        return b.graph()
    return GraphModel(build, image_shape, name="fire_like",
                      precision=precision)
