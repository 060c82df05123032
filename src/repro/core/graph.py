"""Typed operator-IR graph layer: plan a whole network once, serve it.

The per-call ``conv2d`` path builds a ConvSpec and resolves a plan at
every call site; the first graph layer chained same-epilogue convs but
could not express what the paper's evaluation networks actually contain
(residual adds, pooling, fire-module concats, grouped/depthwise convs).
cuDNN moved from per-op descriptors to a graph API for exactly this
reason; this module is that seam for the repo (DESIGN.md §6):

  OpSpec      typed IR node, one frozen dataclass per operator:
              ConvOp (a ConvSpec — including grouped/depthwise),
              PoolOp (max/avg), AddOp (residual, optional ReLU),
              ConcatOp (channel axis), GapOp, DenseOp, NormOp
              (LayerNorm over channels).  Nodes are *named* and name
              their input edges explicitly.
  Graph       a DAG of OpSpec nodes in topological order, shape-checked
              at construction (every edge's producer shape must satisfy
              the consumer).  ``signature()`` is its stable identity —
              schema-versioned key material for the persisted cache.
  GraphPlan   per-conv-node ConvPlans resolved ONCE (keyed by node
              name), one ``explain()`` table for the whole network, a
              ``warmup()`` compile/measure sweep, ``run()`` to execute
              the DAG.
  plan_graph  resolves a GraphPlan, consulting a persisted graph-level
              cache (``$REPRO_CACHE_DIR/graphplans.json``) keyed by
              backend + signature — a warm process constructs the whole
              program with ZERO per-node plan() resolutions.  Entries
              carry a ``schema`` field; unversioned or mismatched
              entries are dropped, never misread.
  PrecisionPolicy
              graph-wide compute dtype (default + per-node overrides)
              landing in each conv node's ``ConvSpec.dtype``, so a whole
              network plans/autotunes/serves in bf16 end to end with
              precision-distinct cache keys (fp32 accumulation is the
              executors' declared behavior).

``ConvGraph`` (the PR-2 chained-ConvSpec API) survives as a thin
compatibility constructor that lowers to the IR; ``plan_graph`` accepts
either.  ``models.cnn`` builds whole forward passes — pools, residuals,
depthwise stages, GAP + dense head — as one planned, bucketable program
that ``serve.cnn.CnnServeEngine`` multiplexes request streams onto.
"""
from __future__ import annotations

import dataclasses
import hashlib
import re
import time
from typing import (Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp

from repro.core.convspec import (ConvPlan, ConvSpec, canonical_dtype,
                                 normalize_pad, normalize_stride, out_size,
                                 plan, resolve_config)
from repro.core.plancache import JsonCache

LayerSpec = Tuple[int, int, int, int]          # (kh, kw, c_out, stride)


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Graph-wide compute-dtype policy: one default plus per-node
    overrides.

    ``PrecisionPolicy("bf16")`` plans every conv node in bfloat16 (all
    built-in executors accumulate fp32 for bf16 inputs — their declared
    ``accum`` behavior); ``overrides={"stem": "fp32"}`` pins named conv
    nodes to another dtype (e.g. a numerically sensitive stem; only
    conv nodes carry a planned dtype, and ``GraphBuilder`` rejects
    overrides naming anything else).  The
    policy lands in each node's ``ConvSpec.dtype``, so every cache key —
    measured autotune, graph signature, persisted graphplans entries —
    is precision-distinct by construction: a bf16 plan can never serve
    an fp32 graph, or vice versa.

    Master params stay fp32; executors cast operands to the node dtype
    at execution time.
    """
    default: str = "float32"
    overrides: Tuple[Tuple[str, str], ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "default", canonical_dtype(self.default))
        ovr = self.overrides
        if isinstance(ovr, Mapping):
            ovr = tuple(sorted(ovr.items()))
        object.__setattr__(self, "overrides", tuple(
            (str(name), canonical_dtype(dt)) for name, dt in ovr))

    @classmethod
    def of(cls, value) -> "PrecisionPolicy":
        """Coerce any accepted spelling (policy | dtype string/dtype |
        None) into a policy; None means fp32."""
        if value is None:
            return cls()
        if isinstance(value, cls):
            return value
        return cls(canonical_dtype(value))

    def dtype_for(self, node_name: str) -> str:
        for name, dt in self.overrides:
            if name == node_name:
                return dt
        return self.default

    def key(self) -> str:
        """Stable identity for plan-memo keys."""
        if not self.overrides:
            return self.default
        ovr = ",".join(f"{n}={d}" for n, d in self.overrides)
        return f"{self.default}[{ovr}]"

    def quantizer(self):
        """The quantization policy riding this precision policy, or None.

        Plain precision policies never quantize; ``quant.QuantPolicy``
        overrides this to return itself — the one hook ``plan_graph``
        threading keys off, so fp callers pay nothing.
        """
        return None

# Persisted graph-plan entry schema.  v1 was the positional
# {"algorithms": [...]} list of the chain era (implicitly unversioned);
# v2 is {"schema": 2, "algorithms": {node_name: algo}} over the IR.
GRAPH_SCHEMA = 2

# graph-level plan cache: {f"{backend}/{signature}": entry}
_STORE = JsonCache("graphplans.json")


def clear_cache() -> None:
    """Drop the in-memory mirror (tests); the JSON file is untouched."""
    _STORE.clear()


# ---------------------------------------------------------------------------
# the operator IR

_NAME_RE = re.compile(r"[A-Za-z0-9_.\-]+")


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """Base IR node: a named operator with explicit input edges."""
    name: str
    inputs: Tuple[str, ...]

    op = "op"                    # overridden per subclass

    def __post_init__(self):
        # names are signature key material: restrict them to a charset
        # disjoint from descriptor() delimiters so signatures can never
        # be ambiguous
        for n in (self.name,) + tuple(self.inputs):
            if not _NAME_RE.fullmatch(n):
                raise ValueError(f"node/edge names must match "
                                 f"[A-Za-z0-9_.-]+; got {n!r}")
        if not self.inputs:
            raise ValueError(f"node {self.name!r} has no inputs")

    # -- IR contract per subclass ---------------------------------------
    def infer_shape(self, in_shapes: Sequence[Tuple[int, ...]]) -> Tuple:
        raise NotImplementedError

    def descriptor(self) -> str:
        """Stable per-node key material (feeds Graph.signature())."""
        return f"{self.op}:{self.name}<{','.join(self.inputs)}>"


@dataclasses.dataclass(frozen=True)
class ConvOp(OpSpec):
    """A planned convolution node (the only node kind plan() resolves).

    A spec carrying a cross-layer ``fused_add`` (the fusion pass's
    residual fold) takes a SECOND input edge — the shortcut operand,
    shape-checked against the conv's output shape; a ``fused_pool``
    spec keeps one input but yields the pooled ``final_shape``.
    """
    spec: ConvSpec = None

    op = "conv"

    def __post_init__(self):
        super().__post_init__()
        if not isinstance(self.spec, ConvSpec):
            raise ValueError(f"conv node {self.name!r} needs a ConvSpec")
        want = 2 if self.spec.fused_add != "none" else 1
        if len(self.inputs) != want:
            raise ValueError(
                f"conv node {self.name!r} takes exactly {want} input(s) "
                f"(fused_add={self.spec.fused_add!r}); got {self.inputs}")

    def infer_shape(self, in_shapes):
        s = in_shapes[0]
        if tuple(s) != self.spec.in_shape:
            raise ValueError(f"conv node {self.name!r} expects input shape "
                             f"{self.spec.in_shape} but edge "
                             f"{self.inputs[0]!r} produces {tuple(s)}")
        if self.spec.fused_add != "none":
            a = tuple(in_shapes[1])
            if a != self.spec.out_shape:
                raise ValueError(
                    f"conv node {self.name!r}: fused-add operand "
                    f"{self.inputs[1]!r} has shape {a} but the conv "
                    f"produces {self.spec.out_shape}")
        return self.spec.final_shape

    def descriptor(self):
        return f"{super().descriptor()}:{self.spec.key()}"


@dataclasses.dataclass(frozen=True)
class PoolOp(OpSpec):
    """Windowed max/avg pooling (NHWC)."""
    kind: str = "max"                         # max | avg
    window: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)

    op = "pool"

    def __post_init__(self):
        super().__post_init__()
        if self.kind not in ("max", "avg"):
            raise ValueError(f"pool node {self.name!r}: kind must be "
                             f"'max' or 'avg'; got {self.kind!r}")
        if len(self.inputs) != 1:
            raise ValueError(f"pool node {self.name!r} takes exactly one "
                             f"input; got {self.inputs}")

    def infer_shape(self, in_shapes):
        (s,) = in_shapes
        if len(s) != 4:
            raise ValueError(f"pool node {self.name!r} needs an NHWC "
                             f"input; got shape {tuple(s)}")
        n, h, w, c = s
        (kh, kw), (sh, sw), (ph, pw) = self.window, self.stride, self.padding
        oh, ow = out_size(h, kh, ph, sh), out_size(w, kw, pw, sw)
        if oh <= 0 or ow <= 0:
            raise ValueError(f"pool node {self.name!r} produces empty "
                             f"output from input {tuple(s)}")
        return (n, oh, ow, c)

    def descriptor(self):
        return (f"{super().descriptor()}:{self.kind}{self.window[0]}x"
                f"{self.window[1]}s{self.stride[0]}x{self.stride[1]}"
                f"p{self.padding[0]}x{self.padding[1]}")


@dataclasses.dataclass(frozen=True)
class AddOp(OpSpec):
    """Elementwise sum of >= 2 same-shape inputs (residual connections);
    optional fused ReLU after the add (the post-residual activation)."""
    activation: str = "none"                  # none | relu

    op = "add"

    def __post_init__(self):
        super().__post_init__()
        if len(self.inputs) < 2:
            raise ValueError(f"add node {self.name!r} needs >= 2 inputs")
        if self.activation not in ("none", "relu"):
            raise ValueError(f"add node {self.name!r}: activation must be "
                             f"'none' or 'relu'; got {self.activation!r}")

    def infer_shape(self, in_shapes):
        first = tuple(in_shapes[0])
        for edge, s in zip(self.inputs, in_shapes):
            if tuple(s) != first:
                raise ValueError(
                    f"add node {self.name!r}: input {edge!r} has shape "
                    f"{tuple(s)} but {self.inputs[0]!r} has {first}")
        return first

    def descriptor(self):
        return f"{super().descriptor()}:{self.activation}"


@dataclasses.dataclass(frozen=True)
class ConcatOp(OpSpec):
    """Channel-axis concatenation (fire-module expand branches)."""

    op = "concat"

    def __post_init__(self):
        super().__post_init__()
        if len(self.inputs) < 2:
            raise ValueError(f"concat node {self.name!r} needs >= 2 inputs")

    def infer_shape(self, in_shapes):
        lead = tuple(in_shapes[0][:-1])
        for edge, s in zip(self.inputs, in_shapes):
            if tuple(s[:-1]) != lead:
                raise ValueError(
                    f"concat node {self.name!r}: input {edge!r} has "
                    f"non-channel dims {tuple(s[:-1])} but "
                    f"{self.inputs[0]!r} has {lead}")
        return lead + (sum(int(s[-1]) for s in in_shapes),)


@dataclasses.dataclass(frozen=True)
class GapOp(OpSpec):
    """Global average pool: (N, H, W, C) -> (N, C) (the classifier neck)."""

    op = "gap"

    def __post_init__(self):
        super().__post_init__()
        if len(self.inputs) != 1:
            raise ValueError(f"gap node {self.name!r} takes exactly one "
                             f"input; got {self.inputs}")

    def infer_shape(self, in_shapes):
        (s,) = in_shapes
        if len(s) != 4:
            raise ValueError(f"gap node {self.name!r} needs an NHWC "
                             f"input; got shape {tuple(s)}")
        return (s[0], s[3])


@dataclasses.dataclass(frozen=True)
class DenseOp(OpSpec):
    """Linear head: (N, C) @ (C, K) [+ b] -> (N, K)."""
    features: Tuple[int, int] = None          # (c_in, c_out)
    bias: bool = True

    op = "dense"

    def __post_init__(self):
        super().__post_init__()
        if (not isinstance(self.features, tuple) or len(self.features) != 2
                or any(int(f) < 1 for f in self.features)):
            raise ValueError(f"dense node {self.name!r} needs features="
                             f"(c_in, c_out); got {self.features!r}")
        if len(self.inputs) != 1:
            raise ValueError(f"dense node {self.name!r} takes exactly one "
                             f"input; got {self.inputs}")

    def infer_shape(self, in_shapes):
        (s,) = in_shapes
        if len(s) != 2 or int(s[1]) != self.features[0]:
            raise ValueError(f"dense node {self.name!r} needs input "
                             f"(N, {self.features[0]}); got {tuple(s)}")
        return (s[0], self.features[1])

    def descriptor(self):
        return (f"{super().descriptor()}:{self.features[0]}x"
                f"{self.features[1]}:bias={int(self.bias)}")


@dataclasses.dataclass(frozen=True)
class NormOp(OpSpec):
    """LayerNorm over the last (channel) axis with a learned scale and
    shift (params ``{"g", "b"}``), computed in float32; takes NHWC or
    ``(N, C)`` inputs (a head's norm follows GAP)."""
    eps: float = 1e-6

    op = "norm"

    def __post_init__(self):
        super().__post_init__()
        if len(self.inputs) != 1:
            raise ValueError(f"norm node {self.name!r} takes exactly one "
                             f"input; got {self.inputs}")
        if not self.eps > 0:
            raise ValueError(f"norm node {self.name!r} needs eps > 0; "
                             f"got {self.eps!r}")

    def infer_shape(self, in_shapes):
        (s,) = in_shapes
        if len(s) not in (2, 4):
            raise ValueError(f"norm node {self.name!r} needs an NHWC or "
                             f"(N, C) input; got shape {tuple(s)}")
        return tuple(s)

    def descriptor(self):
        return f"{super().descriptor()}:eps={self.eps!r}"


@dataclasses.dataclass(frozen=True, eq=False)
class Graph:
    """A DAG of named OpSpec nodes over one graph input.

    ``nodes`` must be in topological order (every edge names the graph
    input or an earlier node — which also rules out cycles); shapes are
    inferred and checked edge-by-edge at construction.  ``output`` names
    the node whose value ``run`` returns (default: the last node).
    """
    nodes: Tuple[OpSpec, ...]
    in_shape: Tuple[int, ...]
    input_name: str = "input"
    output: Optional[str] = None

    def __post_init__(self):
        if not self.nodes:
            raise ValueError("Graph needs at least one node")
        shapes: Dict[str, Tuple[int, ...]] = {
            self.input_name: tuple(map(int, self.in_shape))}
        for node in self.nodes:
            if node.name in shapes:
                raise ValueError(f"duplicate node name {node.name!r}")
            missing = [e for e in node.inputs if e not in shapes]
            if missing:
                raise ValueError(
                    f"node {node.name!r} consumes undefined edge(s) "
                    f"{missing}: nodes must be listed after their inputs "
                    f"(topological order; cycles are impossible)")
            shapes[node.name] = node.infer_shape(
                [shapes[e] for e in node.inputs])
        out = self.output if self.output is not None else self.nodes[-1].name
        if out not in shapes or out == self.input_name:
            raise ValueError(f"output {out!r} is not a node of the graph")
        object.__setattr__(self, "output", out)
        object.__setattr__(self, "shapes", shapes)

    # -- derived ---------------------------------------------------------
    @property
    def out_shape(self) -> Tuple[int, ...]:
        return self.shapes[self.output]

    @property
    def conv_nodes(self) -> Tuple[ConvOp, ...]:
        return tuple(n for n in self.nodes if isinstance(n, ConvOp))

    def node(self, name: str) -> OpSpec:
        for n in self.nodes:
            if n.name == name:
                return n
        raise KeyError(name)

    def signature(self) -> str:
        """Stable graph identity: schema-versioned key material for the
        persisted plan cache."""
        blob = "|".join(
            [f"v{GRAPH_SCHEMA}", f"in{tuple(self.in_shape)}",
             f"out:{self.output}"] + [n.descriptor() for n in self.nodes])
        return hashlib.sha1(blob.encode()).hexdigest()[:16]

    def __len__(self) -> int:
        return len(self.nodes)


class GraphBuilder:
    """Incremental Graph construction with shape threading.

    Each method appends one named node consuming named edges and returns
    the node name, so network definitions read as dataflow:

        b = GraphBuilder((1, 32, 32, 3))
        y = b.conv("stem", "input", 3, 16)
        y = b.pool("pool", y)
        ...
        b.graph()

    Shapes are tracked as nodes are added (conv specs are derived from
    the producer's shape), and the finished ``Graph`` re-validates the
    whole DAG at construction.
    """

    def __init__(self, in_shape, dtype: Union[str, PrecisionPolicy] = "float32",
                 input_name: str = "input"):
        self.in_shape = tuple(map(int, in_shape))
        # ``dtype`` accepts a plain dtype string (every node) or a
        # PrecisionPolicy (default + per-node overrides); model builders
        # pass through whatever GraphModel.graph hands them
        self.precision = PrecisionPolicy.of(dtype)
        self.input_name = input_name
        self.nodes: List[OpSpec] = []
        self.shapes: Dict[str, Tuple[int, ...]] = {
            input_name: self.in_shape}

    @property
    def dtype(self) -> str:
        return self.precision.default

    def _put(self, node: OpSpec) -> str:
        self.shapes[node.name] = node.infer_shape(
            [self.shapes[e] for e in node.inputs])
        self.nodes.append(node)
        return node.name

    def conv(self, name: str, src: str, k, c_out: int, *, stride=1,
             padding="same", epilogue: str = "bias_relu",
             groups: int = 1) -> str:
        kh, kw = (k, k) if isinstance(k, int) else k
        in_shape = self.shapes[src]
        spec = ConvSpec(in_shape, (kh, kw, in_shape[3] // groups, c_out),
                        normalize_stride(stride),
                        normalize_pad(padding, kh, kw),
                        self.precision.dtype_for(name), epilogue, groups)
        return self._put(ConvOp(name, (src,), spec))

    def pool(self, name: str, src: str, *, kind: str = "max", window=2,
             stride=None, padding=0) -> str:
        win = (window, window) if isinstance(window, int) else tuple(window)
        stride = win if stride is None else (
            (stride, stride) if isinstance(stride, int) else tuple(stride))
        pad = (padding, padding) if isinstance(padding, int) \
            else tuple(padding)
        return self._put(PoolOp(name, (src,), kind, win, stride, pad))

    def add(self, name: str, srcs: Sequence[str], *,
            activation: str = "none") -> str:
        return self._put(AddOp(name, tuple(srcs), activation))

    def concat(self, name: str, srcs: Sequence[str]) -> str:
        return self._put(ConcatOp(name, tuple(srcs)))

    def gap(self, name: str, src: str) -> str:
        return self._put(GapOp(name, (src,)))

    def dense(self, name: str, src: str, c_out: int, *,
              bias: bool = True) -> str:
        c_in = int(self.shapes[src][-1])
        return self._put(DenseOp(name, (src,), (c_in, c_out), bias))

    def norm(self, name: str, src: str, eps: float = 1e-6) -> str:
        return self._put(NormOp(name, (src,), float(eps)))

    def graph(self, output: Optional[str] = None) -> Graph:
        # a precision override that names no CONV node is a typo (or a
        # pool/add/dense node, which carries no planned dtype) and would
        # silently no-op — exactly the numerics it was written to protect
        convs = {n.name for n in self.nodes if isinstance(n, ConvOp)}
        ghosts = [n for n, _ in self.precision.overrides if n not in convs]
        if ghosts:
            raise ValueError(
                f"PrecisionPolicy overrides name non-conv node(s) "
                f"{ghosts}; only conv nodes plan a dtype — conv nodes "
                f"here: {sorted(convs)}")
        return Graph(tuple(self.nodes), self.in_shape,
                     self.input_name, output)


# ---------------------------------------------------------------------------
# back-compat: the chained-ConvSpec constructor, lowering to the IR

@dataclasses.dataclass(frozen=True)
class ConvGraph:
    """Ordered chain of ConvSpec nodes (the pre-IR graph API).

    Kept as a thin compatibility constructor: ``plan_graph`` lowers it
    to a ``Graph`` of conv nodes named ``conv0..convN`` via ``to_ir()``
    (see README "Migrating from ConvGraph.chain").
    """
    nodes: Tuple[ConvSpec, ...]

    def __post_init__(self):
        if not self.nodes:
            raise ValueError("ConvGraph needs at least one node")
        for a, b in zip(self.nodes, self.nodes[1:]):
            if a.out_shape != b.in_shape:
                raise ValueError(f"graph chain broken: {a.key()} produces "
                                 f"{a.out_shape} but next node consumes "
                                 f"{b.in_shape}")

    @classmethod
    def chain(cls, layers: Sequence[LayerSpec], in_shape, *,
              padding="same", dtype: str = "float32",
              epilogue: Union[str, Sequence[str]] = "bias_relu"
              ) -> "ConvGraph":
        """Derive the spec chain from a layer list + input geometry.

        ``layers`` uses the SimpleCNN convention ``(kh, kw, c_out,
        stride)``; each node's output geometry feeds the next node.
        ``epilogue`` is one epilogue for every layer, or a per-layer
        sequence (e.g. ``bias_relu`` everywhere but a final ``bias`` on
        a classifier's last conv).
        """
        if isinstance(epilogue, str):
            epilogues = [epilogue] * len(layers)
        else:
            epilogues = list(epilogue)
            if len(epilogues) != len(layers):
                raise ValueError(f"epilogue sequence has {len(epilogues)} "
                                 f"entries for {len(layers)} layers")
        n, h, w, c = map(int, in_shape)
        nodes: List[ConvSpec] = []
        for (kh, kw, co, s), epi in zip(layers, epilogues):
            spec = ConvSpec((n, h, w, c), (kh, kw, c, co),
                            normalize_stride(s), normalize_pad(padding, kh, kw),
                            dtype, epi)
            nodes.append(spec)
            _, h, w, c = spec.out_shape
        return cls(tuple(nodes))

    @property
    def in_shape(self) -> Tuple[int, int, int, int]:
        return self.nodes[0].in_shape

    @property
    def out_shape(self) -> Tuple[int, int, int, int]:
        return self.nodes[-1].out_shape

    def to_ir(self) -> Graph:
        """Lower the chain to the operator IR: conv nodes ``conv{i}``,
        each consuming its predecessor."""
        prev, ops = "input", []
        for i, spec in enumerate(self.nodes):
            name = f"conv{i}"
            ops.append(ConvOp(name, (prev,), spec))
            prev = name
        return Graph(tuple(ops), self.in_shape)

    def signature(self) -> str:
        """Stable graph identity — the lowered IR's signature, so chain
        callers and IR callers share one cache namespace."""
        return self.to_ir().signature()

    def __len__(self) -> int:
        return len(self.nodes)


GraphLike = Union[Graph, ConvGraph]


def _as_ir(graph: GraphLike) -> Graph:
    return graph.to_ir() if isinstance(graph, ConvGraph) else graph


# ---------------------------------------------------------------------------
# cross-layer fusion pass (DESIGN.md §10)

def fuse_graph(graph: Graph, backend: Optional[str] = None
               ) -> Tuple[Graph, Dict[str, str]]:
    """Planning-time IR rewrite: fold fusable consumers into conv nodes.

    Two rewrite rules, applied to fixpoint:

      add   An ``AddOp`` over two edges where one producer is a conv
            with no other consumer, no existing fusion, and epilogue
            ``none``/``bias`` folds into that conv (latest such producer
            in topological order wins).  The conv absorbs the add's
            activation (``fused_add="add"|"add_relu"``), gains the OTHER
            edge as a second input (the shortcut operand), and moves to
            the add's slot — so a ``resnet_like`` shortcut join executes
            inside the conv kernel's epilogue.
      pool  A ``PoolOp`` whose single-consumer conv producer has no
            existing fusion folds into the conv as ``fused_pool``; the
            conv output tile stays in VMEM and is pooled before the
            single writeback.

    Each rewrite is capability-negotiated: it only fires when at least
    one registered executor ``supports()`` the fused spec (executors
    declare fusable forms via ``fusions()``) AND a persisted
    ``tune="full"`` measurement has not ruled the fusion a loss
    (``autotune.fusion_verdict``; unmeasured specs fuse optimistically).
    No ``plan()`` resolution happens here — the pass is pure rewriting,
    so the persisted-cache hit path stays zero-resolution.

    Returns ``(fused_graph, provenance)`` where provenance maps each
    fused conv node name to ``"add:<consumed>"`` / ``"pool:<consumed>"``.
    The original graph object is returned unchanged when nothing fuses.
    """
    from repro.core import autotune, executors
    backend = backend or jax.default_backend()
    nodes: List[OpSpec] = list(graph.nodes)
    output = graph.output
    fused: Dict[str, str] = {}

    def _rename(ns: List[OpSpec], old: str, new: str) -> List[OpSpec]:
        out = []
        for n in ns:
            if old in n.inputs:
                n = dataclasses.replace(n, inputs=tuple(
                    new if e == old else e for e in n.inputs))
            out.append(n)
        return out

    progress = True
    while progress:
        progress = False
        counts: Dict[str, int] = {}
        for n in nodes:
            for e in n.inputs:
                counts[e] = counts.get(e, 0) + 1
        counts[output] = counts.get(output, 0) + 1   # graph output consumes
        index = {n.name: i for i, n in enumerate(nodes)}
        for i, node in enumerate(nodes):
            if isinstance(node, AddOp) and len(node.inputs) == 2:
                best = None
                for pos, e in enumerate(node.inputs):
                    j = index.get(e)
                    if j is None:                    # the graph input
                        continue
                    prod = nodes[j]
                    if (not isinstance(prod, ConvOp)
                            or counts.get(e, 0) != 1
                            or prod.spec.has_fusion
                            or prod.spec.epilogue not in ("none", "bias")):
                        continue
                    if best is None or j > best[0]:
                        best = (j, pos)
                if best is None:
                    continue
                j, pos = best
                conv = nodes[j]
                mode = "add_relu" if node.activation == "relu" else "add"
                spec = dataclasses.replace(conv.spec, fused_add=mode)
                new_inputs = (conv.inputs[0], node.inputs[1 - pos])
                kind = "add"
            elif isinstance(node, PoolOp):
                j = index.get(node.inputs[0])
                if j is None:
                    continue
                conv = nodes[j]
                if (not isinstance(conv, ConvOp)
                        or counts.get(node.inputs[0], 0) != 1
                        or conv.spec.has_fusion):
                    continue
                spec = dataclasses.replace(
                    conv.spec,
                    fused_pool=(node.kind,
                                node.window[0], node.window[1],
                                node.stride[0], node.stride[1],
                                node.padding[0], node.padding[1]))
                new_inputs = conv.inputs
                kind = "pool"
            else:
                continue
            # capability + measured arbitration gates: some executor
            # must support the fused form, and a persisted tune="full"
            # measurement saying the fusion LOSES keeps it unfused
            if not executors.supporting(spec):
                continue
            if autotune.fusion_verdict(spec, backend) is False:
                continue
            fused[conv.name] = f"{kind}:{node.name}"
            # the conv moves into the consumed node's slot (all of its
            # inputs are defined there, and nothing between consumed it)
            nodes[i] = ConvOp(conv.name, new_inputs, spec)
            del nodes[j]
            if output == node.name:
                output = conv.name
            nodes = _rename(nodes, node.name, conv.name)
            progress = True
            break

    if not fused:
        return graph, {}
    return Graph(tuple(nodes), graph.in_shape, graph.input_name,
                 output), fused


# ---------------------------------------------------------------------------
# the planned program

@dataclasses.dataclass
class GraphPlan:
    """Whole-network plan: one resolved ConvPlan per conv node, keyed by
    node name.

    Mutable only through ``warmup(tune=...)`` (``measure=True`` is the
    back-compat spelling of ``tune="algo"``), which may swap node plans
    for measured ``(algorithm, launch config)`` winners; execution
    itself never re-plans.
    """
    graph: Graph
    conv_plans: Dict[str, ConvPlan]
    backend: str
    source: str                  # resolved | graph_cache | forced
    # fusion provenance: {conv node: "add:<consumed>" | "pool:<consumed>"}
    fused: Dict[str, str] = dataclasses.field(default_factory=dict)
    # the pre-fusion IR (None when the pass was disabled): the persisted
    # cache key stays the UNFUSED signature, and tune="full" re-runs the
    # pass from here so measured fused-vs-unfused verdicts can flip a
    # rewrite on or off
    base_graph: Optional[Graph] = None
    # quantization provenance: {conv node: quant.policy.NodeQuant} —
    # covers EVERY conv node when a QuantPolicy planned this graph
    # (int8 nodes carry their scale source, fp nodes the fallback
    # reason); empty on fp plans
    quant: Dict[str, object] = dataclasses.field(default_factory=dict)
    # per-conv-node jitted executables, shared by warmup() and run() so
    # the warmup compile sweep is the same program inference reuses
    _jitted: Dict[str, Callable] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @property
    def node_plans(self) -> Tuple[ConvPlan, ...]:
        """Conv-node plans in graph order (chain-era read surface)."""
        return tuple(self.conv_plans[n.name] for n in self.graph.conv_nodes)

    def _node_fn(self, name: str) -> Callable:
        fn = self._jitted.get(name)
        if fn is None:
            fn = jax.jit(self.conv_plans[name])
            self._jitted[name] = fn
        return fn

    def explain(self) -> str:
        """One aligned table for the whole network (every IR node):
        geometry, dtype, and executor provenance (which registry entry
        won and why — forced / measured / heuristic / cost), with the
        padded share of the launch config's blocks where the executor
        models it (``Executor.padded_share``; 0 when they tile the
        output exactly)."""
        lines = [f"GraphPlan[{self.source}] backend={self.backend} "
                 f"sig={self.graph.signature()} nodes={len(self.graph)}"]
        for node in self.graph.nodes:
            if isinstance(node, ConvOp):
                p = self.conv_plans[node.name]
                s = p.spec
                n, h, w, c = s.in_shape
                kh, kw, _, m = s.filter_shape
                grp = f" g{s.groups}" if s.groups != 1 else ""
                cfg = (f" cfg[{p.config_source}]={p.config.key()}"
                       if p.config else "")
                share = p.executor.padded_share(s, p.config)
                if share is not None:
                    cfg += f" padded={share:.3g}"
                fz = ""
                prov = self.fused.get(node.name)
                if prov:
                    kind, _, consumed = prov.partition(":")
                    fz = f" fused[{kind}]={consumed}"
                qz = ""
                nq = self.quant.get(node.name)
                if nq is not None:
                    qz = f" quant[{nq.label()}]"
                lines.append(
                    f"  {node.name:>8s}  {h:>3d}x{w:<3d} c{c:<4d} {kh}x{kw}/"
                    f"{s.stride[0]}{grp} m{m:<4d} {s.dtype:>9s} -> "
                    f"{p.algorithm:24s} [{p.source}]{cfg}{fz}{qz} {p.reason}")
            else:
                out = self.graph.shapes[node.name]
                lines.append(f"  {node.name:>8s}  {node.descriptor():50s} "
                             f"-> {out}")
        return "\n".join(lines)

    # -- execution -------------------------------------------------------
    def _named_params(self, params) -> Mapping[str, Mapping]:
        """Accept name-keyed params, or the chain-era list of (w, b)
        pairs assigned to conv nodes in graph order."""
        if isinstance(params, Mapping):
            return params
        convs = self.graph.conv_nodes
        pairs = list(params)
        if len(pairs) != len(convs):
            raise ValueError(f"graph has {len(convs)} conv nodes but got "
                             f"{len(pairs)} weight pairs")
        named = {}
        for node, (w, b) in zip(convs, pairs):
            named[node.name] = ({"w": w} if b is None
                                else {"w": w, "b": b})
        return named

    def _node_params(self, params: Mapping, node: OpSpec,
                     wants_bias: bool, weight: str = "w") -> Mapping:
        """One node's param dict, with errors that name the node instead
        of a bare KeyError from inside the DAG walk."""
        p = params.get(node.name)
        if p is None or weight not in p:
            raise ValueError(
                f"params missing {'entry' if p is None else weight} for "
                f"{node.op} node {node.name!r} (param keys: "
                f"{sorted(params)})")
        if wants_bias and "b" not in p:
            raise ValueError(f"{node.op} node {node.name!r} wants a bias "
                             f"but params carry none")
        return p

    def run(self, x, params, observe: Optional[Callable] = None):
        """Execute the DAG on ``x``.

        ``params``: ``{node_name: {"w": ..., "b": ...}}`` for conv and
        dense nodes (``b`` only where the node wants one) and
        ``{"g": ..., "b": ...}`` for norm nodes, or — for
        graphs lowered from ``ConvGraph.chain`` — the legacy list of
        one ``(w, bias)`` pair per conv node in graph order.  No plan()
        resolution happens here — the program was resolved up front.

        ``observe``, when given, is called as ``observe(name, value)``
        with every conv node's INPUT activation (a concrete array —
        only the per-node executables are jitted, not the DAG walk);
        the calibration collector rides this hook.
        """
        params = self._named_params(params)
        values = {self.graph.input_name: x}
        for node in self.graph.nodes:
            # every op a node lowers to carries the node's name in its
            # op_name metadata, so a device trace can attribute it
            with jax.named_scope(node.name):
                values[node.name] = self._run_node(
                    node, [values[e] for e in node.inputs], params, observe)
        return values[self.graph.output]

    def _run_node(self, node: OpSpec, ins: Sequence, params: Mapping,
                  observe: Optional[Callable]):
        """One node's output from its input values."""
        from repro.kernels import ops
        if isinstance(node, ConvOp):
            if observe is not None:
                observe(node.name, ins[0])
            p = self._node_params(params, node, node.spec.has_bias)
            a = ins[1] if node.spec.fused_add != "none" else None
            return self._node_fn(node.name)(
                ins[0], p["w"], p["b"] if node.spec.has_bias else None, a)
        if isinstance(node, PoolOp):
            return ops.pool2d(ins[0], node.kind, node.window,
                              node.stride, node.padding)
        if isinstance(node, AddOp):
            y = ins[0]
            for other in ins[1:]:
                y = y + other
            return jax.nn.relu(y) if node.activation == "relu" else y
        if isinstance(node, ConcatOp):
            return jnp.concatenate(ins, axis=-1)
        if isinstance(node, GapOp):
            return ins[0].mean(axis=(1, 2))
        if isinstance(node, DenseOp):
            p = self._node_params(params, node, node.bias)
            y = ins[0] @ p["w"]
            return y + p["b"] if node.bias else y
        if isinstance(node, NormOp):
            p = self._node_params(params, node, True, weight="g")
            x = ins[0].astype(jnp.float32)
            mu = x.mean(axis=-1, keepdims=True)
            var = jnp.square(x - mu).mean(axis=-1, keepdims=True)
            y = (x - mu) * jax.lax.rsqrt(var + node.eps) * p["g"] + p["b"]
            return y.astype(ins[0].dtype)
        raise TypeError(f"unknown IR node type {type(node)}")

    def _attach_quant(self) -> None:
        """Re-attach the quantization payload (calibrated activation
        scale) to int8 node plans — needed after any re-resolution,
        since plan() knows nothing of calibration."""
        from repro.quant.policy import QuantInfo
        for name, nq in self.quant.items():
            if getattr(nq, "quantized", False) and name in self.conv_plans:
                self.conv_plans[name] = dataclasses.replace(
                    self.conv_plans[name],
                    quant=QuantInfo(nq.x_scale, nq.source))

    # -- warmup / autotune ----------------------------------------------
    def warmup(self, *, measure: bool = False,
               tune: Optional[str] = None, repeats: int = 3,
               calibrate: Optional[object] = None) -> Dict:
        """Compile (and optionally measure-autotune) every conv node in
        one sweep.

        ``tune="algo"`` runs the exhaustive per-node executor timing
        sweep (``autotune.tune_spec`` with the node's epilogue and
        groups threaded through); ``tune="full"`` then sweeps each
        winner's candidate *launch configs* (VMEM-pruned before timing).
        Either re-resolves each conv node against the freshly persisted
        winners and re-persists the graph-level entry — after which the
        plan serves inference with zero further plan() resolutions and
        zero re-measurement.  ``measure=True`` is the back-compat
        spelling of ``tune="algo"``.

        ``calibrate`` takes a ``quant.Calibrator`` (sample batch +
        params + observer choice): the plan runs over the batch first,
        recording every conv node's input activation range into the
        persisted ``calibration.json`` — the scales a later
        ``QuantPolicy``-planned graph quantizes with (DESIGN.md §13).

        Returns ``{"nodes": [...], "total_ms": float}`` with one
        algorithm/config/source/compile-time row per conv node (plus a
        ``"calibration"`` entry map when ``calibrate`` ran).
        """
        from repro.core import autotune
        if measure and tune is None:
            tune = "algo"
        t_start = time.perf_counter()
        calib_entries = None
        if calibrate is not None:
            calib_entries = calibrate.collect(self)
        if tune is not None:
            # tune-mode and backend-mismatch validation live in
            # tune_spec (one home), which raises before any node is
            # measured
            for node in self.graph.conv_nodes:
                autotune.tune_spec(node.spec, tune=tune,
                                   backend=self.backend, repeats=repeats)
            if tune == "full" and self.base_graph is not None:
                # tune="full" measured each fused spec against its
                # unfused decomposition (autotune.measure_fusion); re-run
                # the pass from the pre-fusion IR so losing rewrites are
                # dropped — and previously vetoed ones re-admitted
                refused, fmap = fuse_graph(self.base_graph, self.backend)
                if refused.signature() != self.graph.signature():
                    old = {n.name: n.spec for n in self.graph.conv_nodes}
                    self.graph, self.fused = refused, fmap
                    for node in self.graph.conv_nodes:
                        if old.get(node.name) != node.spec:
                            autotune.tune_spec(node.spec, tune=tune,
                                               backend=self.backend,
                                               repeats=repeats)
            self.conv_plans = {n.name: plan(n.spec, backend=self.backend)
                               for n in self.graph.conv_nodes}
            self._attach_quant()        # re-resolution dropped the scales
            self._jitted.clear()        # stale traces must not serve on
            _persist(self.base_graph or self.graph, self.backend,
                     self.conv_plans, alias=self.graph)
        rows = []
        for node in self.graph.conv_nodes:
            p = self.conv_plans[node.name]
            s = p.spec
            dtype = jnp.dtype(s.dtype)
            x = jnp.zeros(s.in_shape, dtype)
            w = jnp.zeros(s.filter_shape, dtype)
            b = jnp.zeros((s.filter_shape[3],), dtype) if s.has_bias else None
            a = (jnp.zeros(s.out_shape, dtype)
                 if s.fused_add != "none" else None)
            t0 = time.perf_counter()
            self._node_fn(node.name)(x, w, b, a).block_until_ready()
            rows.append({"node": node.name, "key": s.key(),
                         "algorithm": p.algorithm, "source": p.source,
                         "config": (p.config.as_dict() if p.config else {}),
                         "config_source": p.config_source,
                         "compile_ms": (time.perf_counter() - t0) * 1e3})
        out = {"nodes": rows,
               "total_ms": (time.perf_counter() - t_start) * 1e3}
        if calib_entries is not None:
            out["calibration"] = calib_entries
        return out


# ---------------------------------------------------------------------------
# resolution + persisted graph-level cache

def plan_graph(graph: GraphLike, *, backend: Optional[str] = None,
               force: Optional[str] = None,
               use_cache: bool = True, fuse: bool = True,
               quant: Optional[object] = None) -> GraphPlan:
    """Resolve a whole-network plan once.

    Accepts the IR (``Graph``) or the compatibility chain
    (``ConvGraph``, lowered via ``to_ir``).  A ``quant`` policy
    (``quant.QuantPolicy``) runs the int8 quantize pass over the IR
    first — eligible conv nodes' specs flip to int8 (DESIGN.md §13) —
    so everything downstream (fusion, cache keys, autotune) sees the
    quantized graph and is dtype-distinct by construction.  The
    cross-layer fusion pass (``fuse_graph``) rewrites the IR next —
    ``fuse=False`` is the escape hatch serving the unfused program.
    Forced plans bypass the persisted cache in both directions (they
    are a debugging/benchmark tool, not a deployment choice).
    Otherwise a persisted entry keyed by backend + the PRE-fusion graph
    signature (so callers address the cache by the graph they wrote,
    not the pass's output) reconstructs the program with zero per-node
    plan() resolutions; entries that are unversioned, carry a foreign
    schema, or name unknown / no-longer-supported algorithms are
    dropped and re-resolved.
    """
    ir = _as_ir(graph)
    backend = backend or jax.default_backend()
    qprov: Dict[str, object] = {}
    qinfos: Dict[str, object] = {}
    if quant is not None:
        from repro.quant.policy import quantize_graph
        ir, qprov, qinfos = quantize_graph(ir, quant, backend)
    fmap: Dict[str, str] = {}
    base = ir if fuse else None
    prog = ir
    if fuse:
        prog, fmap = fuse_graph(ir, backend)

    def _attach(plans: Dict[str, ConvPlan]) -> Dict[str, ConvPlan]:
        for name, qi in qinfos.items():
            if name in plans:
                plans[name] = dataclasses.replace(plans[name], quant=qi)
        return plans

    if force is not None:
        plans = {n.name: plan(n.spec, force=force, backend=backend)
                 for n in prog.conv_nodes}
        return GraphPlan(prog, _attach(plans), backend, "forced",
                         fused=fmap, base_graph=base, quant=qprov)
    if use_cache:
        cached = _plans_from_cache(prog, backend, key_graph=ir)
        if cached is not None:
            return GraphPlan(prog, _attach(cached), backend, "graph_cache",
                             fused=fmap, base_graph=base, quant=qprov)
    plans = {n.name: plan(n.spec, backend=backend) for n in prog.conv_nodes}
    if use_cache:       # use_cache=False means no cache interaction AT ALL
        _persist(ir, backend, plans, alias=prog)
    return GraphPlan(prog, _attach(plans), backend, "resolved",
                     fused=fmap, base_graph=base, quant=qprov)


def _graph_key(graph: GraphLike, backend: str) -> str:
    return f"{backend}/{graph.signature()}"


def _persist(graph: Graph, backend: str, plans: Mapping[str, ConvPlan],
             alias: Optional[Graph] = None) -> None:
    # ``graph`` is the addressing identity (the pre-fusion IR); when the
    # fusion pass rewrote it, ``alias`` is the fused program, which gets
    # the same entry under its own signature so callers holding either
    # graph can find it (reads go through the pre-fusion key)
    entry = {"schema": GRAPH_SCHEMA,
             "algorithms": {name: p.algorithm
                            for name, p in plans.items()}}
    _STORE.put(_graph_key(graph, backend), entry)
    if alias is not None and alias.signature() != graph.signature():
        _STORE.put(_graph_key(alias, backend), entry)


def _plans_from_cache(graph: Graph, backend: str,
                      key_graph: Optional[Graph] = None
                      ) -> Optional[Dict[str, ConvPlan]]:
    # ``graph`` is the (possibly fused) program whose conv specs the
    # entry must satisfy; ``key_graph`` is the pre-fusion IR the entry
    # is addressed by (fusion keeps conv node NAMES stable, so one entry
    # serves both the fused and unfused program of the same source IR)
    from repro.core import autotune, executors
    entry = _STORE.get(_graph_key(key_graph or graph, backend))
    if not isinstance(entry, dict):
        return None
    if entry.get("schema") != GRAPH_SCHEMA:
        return None       # unversioned / foreign-schema entry: never decode
    algos = entry.get("algorithms")
    conv_nodes = graph.conv_nodes
    if (not isinstance(algos, dict)
            or set(algos) != {n.name for n in conv_nodes}):
        return None
    plans: Dict[str, ConvPlan] = {}
    for node in conv_nodes:
        algo = algos[node.name]
        spec = node.spec
        if not executors.capable(algo, spec):
            return None                 # stale entry: caller re-resolves
        # a measured winner recorded since this entry was persisted must
        # win (plan()'s measured > heuristic precedence survives the
        # graph layer): treat the entry as stale and re-resolve
        measured = autotune.cached_best(spec, backend)
        if (measured is not None and measured != algo
                and executors.capable(measured, spec)):
            return None
        # launch configs are per-spec state (autotune.json), not part of
        # the graph entry: re-resolve so a measured config recorded
        # since — or one gone stale — is honored without re-measurement
        cfg, cfg_src = resolve_config(spec, algo, backend)
        plans[node.name] = ConvPlan(spec, algo, "graph_cache",
                                    "persisted graph-level plan", backend,
                                    config=cfg, config_source=cfg_src)
    return plans
