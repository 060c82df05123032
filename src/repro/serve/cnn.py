"""Batch-bucketed CNN serving over graph-planned programs.

The LM engine (serve/engine.py) keeps its compiled surface to two jitted
functions over fixed shapes; this engine applies the same discipline to
CNN inference traffic: the ONLY compiled programs are one jitted
whole-network GraphPlan execution per configured batch *bucket*.  Any
model exposing ``graph_plan``/``apply`` over the operator IR plugs in —
including the real network shapes (``resnet_like`` residual blocks,
``mobilenet_like`` depthwise stages, ``fire_like`` concats) whose whole
forward pass, head included, is one planned program.
Incoming image requests (each carrying one image or a small batch) are
flattened into per-image units and multiplexed onto the largest bucket
that fits the remaining queue — short remainders ride the smallest
bucket with zero-padded slots.  Plans are resolved once per bucket (and
persisted via the graph-level cache), so a warm engine serves any
request mix with zero plan() resolutions and at most ``len(buckets)``
compiled shapes.  A graph-wide ``PrecisionPolicy`` (``precision="bf16"``)
plans every bucket program in reduced precision end to end — fp32
master params, fp32 accumulation, precision-distinct cache keys.

Bucket-program building lives in ``BucketPrograms`` so the synchronous
drain engine here and the continuous-batching ``AsyncServeFrontend``
(serve/frontend.py) share one component: one geometry, one bucket set,
one packing dtype (``input_dtype()`` — warmup compiles exactly the
trace that serves), at most ``len(buckets)`` compiled programs.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import sharding as _sh


@dataclasses.dataclass
class ImageRequest:
    """Images to classify.  ``images`` must not be mutated until the
    request is done: a serving layer may read them in place (the
    frontend's feed path puts a request's block as its own array)."""
    rid: int
    images: np.ndarray                  # (n, H, W, C), or (H, W, C) for one
    out: Optional[np.ndarray] = None    # (n, num_classes) once served
    done: bool = False

    def __post_init__(self):
        self.images = np.asarray(self.images)
        if self.images.ndim == 3:
            self.images = self.images[None]
        if self.images.ndim != 4:
            raise ValueError(f"images must be (n, H, W, C) or (H, W, C); "
                             f"got shape {self.images.shape}")


# ---------------------------------------------------------------------------
# packing: per-image units -> one contiguous batch array

def contiguous_blocks(chunk: Sequence[Tuple[ImageRequest, int]]
                      ) -> List[Tuple[ImageRequest, int, int]]:
    """Collapse ``(request, image_index)`` units into maximal contiguous
    ``(request, i0, i1)`` slices — units are generated in per-request
    index order, so consecutive units of one request always coalesce."""
    blocks: List[List] = []
    for r, i in chunk:
        if blocks and blocks[-1][0] is r and blocks[-1][2] == i:
            blocks[-1][2] = i + 1
        else:
            blocks.append([r, i, i + 1])
    return [tuple(b) for b in blocks]


def pack_units(chunk: Sequence[Tuple[ImageRequest, int]], bucket: int,
               image_shape: Tuple[int, int, int],
               dtype: np.dtype) -> np.ndarray:
    """Stack a chunk of units into a ``(bucket, H, W, C)`` batch in one
    vectorized pass: contiguous request slices are concatenated (no
    per-image copy loop) and short chunks get zero-padded tail slots.
    Every slice is cast to ``dtype`` so the packed batch always matches
    the dtype the bucket programs were compiled for."""
    parts = [np.asarray(r.images[i0:i1], dtype)
             for r, i0, i1 in contiguous_blocks(chunk)]
    pad = bucket - len(chunk)
    if pad:
        parts.append(np.zeros((pad,) + tuple(image_shape), dtype))
    return np.concatenate(parts, axis=0)


def _concatenate(xs):
    return jnp.concatenate(xs, axis=0)


# the feed path's device-side assembly of a batch from its slices: its
# own small program (module ``jit_assemble``) beside the bucket program
_concatenate.__name__ = _concatenate.__qualname__ = "assemble"
_assemble = jax.jit(_concatenate)


def scatter_outputs(chunk: Sequence[Tuple[ImageRequest, int]],
                    y: np.ndarray) -> None:
    """Write batch outputs back into each request's ``out`` rows,
    block-wise (the inverse of ``pack_units``; padded rows ignored)."""
    off = 0
    for r, i0, i1 in contiguous_blocks(chunk):
        if r.out is None:
            # empty, not zeros: every row is written exactly once (a
            # dispatched request is committed — all its units serve)
            r.out = np.empty((r.images.shape[0], y.shape[-1]), y.dtype)
        r.out[i0:i1] = y[off:off + (i1 - i0)]
        off += i1 - i0


# ---------------------------------------------------------------------------
# the reusable bucket-program component

class BucketPrograms:
    """One geometry's bucket programs: build, warm, pick, pack.

    Owns the ``{bucket: jitted whole-network program}`` table for one
    ``(image_shape, buckets)`` pair — the component both serving layers
    are built from (``CnnServeEngine`` holds one; ``AsyncServeFrontend``
    holds one per geometry).  ``input_dtype()`` is the single source of
    truth for the dtype requests are packed to AND the dtype
    ``warmup()``'s dummy compiles, so a warm program can never be asked
    to retrace at serve time because the two paths disagreed.

    **Sharded mode** (``mesh=`` a 1-D ``('data',)`` mesh from
    ``launch.mesh.make_serve_mesh``): the configured ``buckets`` become
    PER-SHARD capacities and the served (global) buckets are
    ``bucket * mesh_size`` — device-count-aware by construction, every
    global bucket a multiple of the mesh size, padding accounted per
    shard (``shard_units``).  Each program is the per-shard-geometry
    ``GraphPlan`` — so tuned launch configs persisted in autotune.json
    for that geometry are reused per shard unchanged — wrapped in
    ``shard_map`` over the mesh and jitted with the batch axis sharded
    and params replicated.  Because the per-shard body is traced at the
    per-shard batch shape, outputs are bitwise-identical to the
    single-device program at that bucket, whatever the device count.
    """

    def __init__(self, model, params, image_shape: Tuple[int, int, int], *,
                 buckets: Tuple[int, ...] = (1, 4, 8), algorithm="auto",
                 backend: Optional[str] = None, precision=None,
                 fuse: bool = True, input_dtype=None, mesh=None):
        self.model = model
        self.image_shape = tuple(map(int, image_shape))     # (H, W, C)
        self.mesh = mesh
        self.n_shards = int(np.prod(mesh.devices.shape)) if mesh else 1
        self.shard_buckets = tuple(sorted({int(b) for b in buckets}))
        if not self.shard_buckets or self.shard_buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints; got {buckets}")
        # the buckets traffic is packed to: global batch sizes
        self.buckets = tuple(b * self.n_shards for b in self.shard_buckets)
        self.algorithm = algorithm
        self.backend = backend or jax.default_backend()
        self.precision = precision
        self.fuse = fuse
        self._input_dtype = np.dtype(input_dtype or np.float32)
        self._fns: Dict[int, Callable] = {}    # global bucket -> program
        self._zero_slice = None                 # feed path, made once
        self._plans: Dict[int, object] = {}    # global bucket -> GraphPlan
        # built once: NamedSharding construction is ~0.1ms of pure
        # Python, far too hot to repeat on every packed batch
        self._in_sharding = (None if mesh is None
                             else _sh.batch_sharded(mesh, ndim=4))
        # replicate params once onto the mesh (a tree already replicated
        # there — e.g. by a dispatcher shared across geometries — passes
        # through without any transfer)
        self.params = (params if mesh is None
                       else _sh.replicate_params(params, mesh))

    # ------------------------------------------------------------------
    def input_dtype(self) -> np.dtype:
        """The one packing/compile dtype.  Host inputs stay fp32 by
        default regardless of the PrecisionPolicy — the planned conv
        nodes cast operands to their spec dtype, and master inputs
        (like master params) are served full-precision.  Engines built
        with ``input_dtype=`` feed that dtype instead; either way,
        ``warmup`` and the packers both read THIS value."""
        return self._input_dtype

    @property
    def compiled_buckets(self) -> Tuple[int, ...]:
        """Batch sizes with a built program — never exceeds ``buckets``."""
        return tuple(sorted(self._fns))

    def serve_dtype(self, b: int) -> str:
        """The compute dtype(s) global bucket ``b``'s program serves its
        conv nodes in — ``"int8"`` for a fully quantized graph,
        ``"float32+int8"`` for a QuantPolicy with fp fallback nodes,
        ``"bfloat16"``/``"float32"`` for plain precision policies.
        Builds the bucket's plan on first use (same path as ``fn``)."""
        gp = self.graph_plan(b)
        dtypes = sorted({p.spec.dtype for p in gp.conv_plans.values()})
        return "+".join(dtypes) if dtypes else str(self._input_dtype)

    def graph_plan(self, b: int):
        """The GraphPlan global bucket ``b``'s program runs (per-shard
        geometry in sharded mode; built on first use, as ``fn``)."""
        if b not in self._plans:
            self.fn(b)
        return self._plans[b]

    def serve_dtypes(self) -> Dict[int, str]:
        """``{global bucket: serving dtype}`` over the configured
        buckets (plans are resolved as needed — cached thereafter)."""
        return {b: self.serve_dtype(b) for b in self.buckets}

    def pick_bucket(self, pending: int) -> int:
        """Largest bucket the pending unit count fills, else the
        smallest bucket (its tail slots ride zero-padded)."""
        fits = [b for b in self.buckets if b <= pending]
        return max(fits) if fits else self.buckets[0]

    def input_sharding(self):
        """How packed batches land on devices: batch axis sharded over
        the mesh, or None (default placement) unsharded — the value
        ``put()`` and the dispatch paths hand to ``jax.device_put``."""
        return self._in_sharding

    def put(self, xb: np.ndarray):
        """Explicitly place one packed batch (host → device(s)).  The
        serving layers only ever move inputs through here, so a
        ``jax.transfer_guard("disallow")`` around a warm serve loop
        proves params are never re-transferred."""
        return jax.device_put(xb, self.input_sharding())

    def shard_units(self, real: int, b: int) -> Optional[List[int]]:
        """Real (non-padded) images per mesh device for a batch of
        ``real`` units packed to global bucket ``b`` — shards take
        contiguous row slices, so padding concentrates in the trailing
        devices.  None when unsharded."""
        if self.mesh is None:
            return None
        per = b // self.n_shards
        return [max(0, min(per, real - i * per))
                for i in range(self.n_shards)]

    def _shard_plan(self, b: int):
        """The per-shard GraphPlan for global bucket ``b`` — the SAME
        plan (and tuned autotune.json launch configs) a single-device
        engine resolves for that per-shard batch geometry."""
        bs = b // self.n_shards
        return self.model.graph_plan(
            (bs,) + self.image_shape, backend=self.backend,
            force=None if self.algorithm == "auto" else self.algorithm,
            precision=self.precision, fuse=self.fuse)

    def fn(self, b: int) -> Callable:
        """The jitted program for global bucket ``b`` (built on first
        use).  Sharded mode wraps the per-shard program in ``shard_map``
        over the mesh: params replicated, batch axis split, outputs
        row-sharded — and the per-shard body traced at exactly the
        per-shard batch shape (bitwise parity with the single-device
        program)."""
        f = self._fns.get(b)
        if f is None:
            gp = self._shard_plan(b)
            self._plans[b] = gp

            def program(params, xb):
                return self.model.apply(params, xb, graph_plan=gp)
            # a stable per-bucket name: the compiled module (and each
            # of its runs in a device trace) is jit_serve_b<bucket>
            program.__name__ = program.__qualname__ = f"serve_b{b}"
            if self.mesh is None:
                f = jax.jit(program)
            else:
                from jax.sharding import PartitionSpec as P
                # check_vma=False: the per-shard body has no collectives,
                # and pallas_call outputs carry no varying-axes type for
                # the check to read
                body = jax.shard_map(
                    program,
                    mesh=self.mesh,
                    in_specs=(P(), P("data", None, None, None)),
                    out_specs=P("data"), check_vma=False)
                # out sharding names only the leading (batch) dim so
                # any output rank stays row-sharded
                f = jax.jit(
                    body,
                    in_shardings=(_sh.replicated(self.mesh),
                                  self.input_sharding()),
                    out_shardings=_sh.batch_sharded(self.mesh, ndim=1))
            self._fns[b] = f
        return f

    def pack(self, chunk: Sequence[Tuple[ImageRequest, int]],
             bucket: int) -> np.ndarray:
        return pack_units(chunk, bucket, self.image_shape,
                          self.input_dtype())

    # -- the feed path: a batch as request-aligned slices -----------------
    def slice_size(self, b: int) -> int:
        """Images per slice of global bucket ``b`` on the feed path: the
        smallest bucket where it divides ``b``, else ``b`` (one slice)."""
        s = self.buckets[0]
        return s if b % s == 0 else b

    def zero_slice(self):
        """The all-padding slice of the smallest bucket, on the device:
        made once, never transferred again."""
        if self._zero_slice is None:
            self._zero_slice = self.put(np.zeros(
                (self.buckets[0],) + self.image_shape, self.input_dtype()))
        return self._zero_slice

    def slices(self, chunk: Sequence[Tuple[ImageRequest, int]],
               bucket: int) -> Tuple[list, int]:
        """Global bucket ``bucket``'s input as ``bucket / slice_size``
        slices, and how many of them are views.  A slice that is one
        contiguous block of one request, already C-contiguous in
        ``input_dtype()``, is that request's own array (a view, no
        copy); a slice mixing requests, cast or partly padded is packed
        alone at slice size; an all-padding slice is ``zero_slice()``.
        The request's images are read in place until the put is done."""
        s, dtype = self.slice_size(bucket), self.input_dtype()
        out: list = []
        views = 0
        for k in range(0, bucket, s):
            part = chunk[k:k + s]
            if not part:
                out.append(self.zero_slice())
                continue
            blocks = contiguous_blocks(part)
            if len(part) == s and len(blocks) == 1:
                r, i0, i1 = blocks[0]
                view = r.images[i0:i1]
                if view.dtype == dtype and view.flags.c_contiguous:
                    out.append(view)
                    views += 1
                    continue
            out.append(pack_units(part, s, self.image_shape, dtype))
        return out, views

    def assemble(self, xs: Sequence):
        """One batch from its slices already on the device: a
        concatenation in a jitted step of its own (one slice is the
        batch itself)."""
        return xs[0] if len(xs) == 1 else _assemble(list(xs))

    def warm_feed(self, b: int) -> None:
        """Compile the feed path's assembly for global bucket ``b`` (and
        make the zero slice), so the fed batch runs warm programs
        only."""
        k = b // self.slice_size(b)
        if k > 1:
            jax.block_until_ready(self.assemble([self.zero_slice()] * k))

    def warmup(self, *, measure: bool = False,
               tune: Optional[str] = None) -> Dict[int, float]:
        """Resolve + compile every bucket program in one sweep.

        ``tune="algo"`` first measure-autotunes each bucket's graph
        (GraphPlan.warmup) and ``tune="full"`` also sweeps the winning
        executors' candidate launch configs, so the compiled programs
        embed the measured ``(algorithm, config)`` winners — a served
        graph is tuned once here and replayed from cache ever after.
        ``measure=True`` is the back-compat spelling of ``tune="algo"``.
        The compile dummy is ``input_dtype()`` — exactly the dtype the
        packers feed — so warmup compiles exactly the trace that serves.
        Sharded mode tunes the PER-SHARD geometry (that is what each
        device executes) and places the dummy with the batch sharding.
        Returns per-bucket compile milliseconds keyed by global bucket.
        """
        if measure and tune is None:
            tune = "algo"
        H, W, C = self.image_shape
        out = {}
        for b in self.buckets:
            if tune is not None and self.algorithm == "auto":
                bs = b // self.n_shards
                self.model.graph_plan((bs, H, W, C), backend=self.backend,
                                      precision=self.precision,
                                      fuse=self.fuse) \
                    .warmup(tune=tune)
                # the measured sweep may have swapped node plans: an
                # already-compiled program would keep serving the stale
                # trace, so force a rebuild
                self._fns.pop(b, None)
                self._plans.pop(b, None)
            f = self.fn(b)
            x = self.put(np.zeros((b, H, W, C), self.input_dtype()))
            t0 = time.perf_counter()
            f(self.params, x).block_until_ready()
            out[b] = (time.perf_counter() - t0) * 1e3
        return out


# ---------------------------------------------------------------------------
# the synchronous drain engine

class CnnServeEngine:
    """Serve image-classification traffic through batch-bucketed plans."""

    def __init__(self, model, params, image_shape: Tuple[int, int, int], *,
                 buckets: Tuple[int, ...] = (1, 4, 8), algorithm="auto",
                 backend: Optional[str] = None, precision=None,
                 fuse: bool = True, input_dtype=None, mesh=None):
        # graph-wide PrecisionPolicy (e.g. "bf16") for every bucket
        # program; None defers to the model's own policy / fp32 inputs.
        # Master params stay fp32 — conv nodes cast per their specs, so
        # the same engine params serve any policy.  fuse=False serves
        # every bucket's unfused program (mirrors plan_graph's hatch).
        # mesh= shards every bucket program data-parallel (see
        # BucketPrograms; serve/distributed.py for the scheduler story).
        self.programs = BucketPrograms(
            model, params, image_shape, buckets=buckets,
            algorithm=algorithm, backend=backend, precision=precision,
            fuse=fuse, input_dtype=input_dtype, mesh=mesh)
        self.queue: List[ImageRequest] = []
        self.stats = {"requests": 0, "images": 0, "padded_slots": 0,
                      "batches": {b: 0 for b in self.programs.buckets}}

    # -- thin views over the shared component --------------------------
    @property
    def model(self):
        return self.programs.model

    @property
    def params(self):
        return self.programs.params

    @property
    def image_shape(self) -> Tuple[int, int, int]:
        return self.programs.image_shape

    @property
    def buckets(self) -> Tuple[int, ...]:
        return self.programs.buckets

    @property
    def precision(self):
        return self.programs.precision

    @property
    def compiled_buckets(self) -> Tuple[int, ...]:
        return self.programs.compiled_buckets

    @property
    def _fns(self) -> Dict[int, Callable]:
        # the live program table (tests and callers may inspect/patch it)
        return self.programs._fns

    def serve_dtypes(self) -> Dict[int, str]:
        """Per-bucket serving dtype (see ``BucketPrograms.serve_dtype``)
        — ``"int8"`` buckets are proof the engine serves quantized."""
        return self.programs.serve_dtypes()

    def _bucket_fn(self, b: int) -> Callable:
        return self.programs.fn(b)

    def _pick_bucket(self, pending: int) -> int:
        return self.programs.pick_bucket(pending)

    def warmup(self, *, measure: bool = False,
               tune: Optional[str] = None) -> Dict[int, float]:
        """Resolve + compile every bucket program (see
        ``BucketPrograms.warmup``)."""
        return self.programs.warmup(measure=measure, tune=tune)

    # ------------------------------------------------------------------
    def submit(self, req: ImageRequest) -> None:
        if tuple(req.images.shape[1:]) != self.image_shape:
            raise ValueError(f"request {req.rid}: image shape "
                             f"{req.images.shape[1:]} != engine shape "
                             f"{self.image_shape}")
        self.queue.append(req)

    def run(self) -> List[ImageRequest]:
        """Drain the queue; returns the served requests (outputs filled).

        Requests are flattened to per-image units and packed batch by
        batch: the largest bucket that the remaining unit count fills,
        else the smallest bucket with padded (zero) slots.
        """
        served, units = list(self.queue), []
        for r in served:
            units.extend((r, i) for i in range(r.images.shape[0]))
        cursor = 0
        while cursor < len(units):
            b = self.programs.pick_bucket(len(units) - cursor)
            chunk = units[cursor:cursor + b]
            xb = self.programs.pack(chunk, b)
            y = np.asarray(self.programs.fn(b)(self.params,
                                               self.programs.put(xb)))
            scatter_outputs(chunk, y)
            self.stats["batches"][b] += 1
            self.stats["padded_slots"] += b - len(chunk)
            self.stats["images"] += len(chunk)
            cursor += b
        # only a fully drained queue is cleared: a failure above leaves
        # every request submitted (outputs rewrite idempotently on retry)
        self.queue = []
        self.stats["requests"] += len(served)
        for r in served:
            r.done = True
        return served
