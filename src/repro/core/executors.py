"""Executor registry: the open menu of convolution algorithms.

cuDNN's deployment story — the one the paper leans on ("frameworks
automatically select the best-performing convolution algorithm for each
layer") — is an *algorithm enum plus capability query*: a menu of
implementations, each answering "can you run this descriptor?" before
anyone asks "how fast?".  This module is that seam as a first-class,
third-party-extensible API (DESIGN.md §8).  Every algorithm is a
registered ``Executor`` object declaring:

  name             stable string identity — what ``ConvPlan.algorithm``,
                   ``conv2d(algorithm=...)`` and the persisted
                   autotune/graphplans cache entries resolve through
  dtypes / accum   supported input dtypes and accumulation behavior
                   (every built-in accumulates fp32 for bf16 inputs via
                   ``preferred_element_type`` or an f32 VMEM accumulator)
  supports(spec)   exact capability over stride / groups / kernel size /
                   dtype / VMEM working set
  heuristic_claim  the executor's claim on the paper's empirical regions
                   (figs 5-7), scored so negotiation can rank rivals
  cost(spec)       abstract cost model (MACs + weighted extra HBM
                   traffic) for the cheapest-supported tier
  vmem_bytes(spec, config)
                   optional VMEM working-set model (also the pre-
                   measurement pruner for candidate launch configs)
  configs(spec)    ordered candidate *launch configs* (tile sizes,
                   rows-per-step; DESIGN.md §9) — candidate 0 is the
                   historical hard-coded geometry; ``config_supports``
                   prunes, ``default_config`` model-picks absent
                   measurement, ``autotune.measure_config`` sweeps
  epilogues        the ``ConvSpec.epilogue`` values it runs (every one
                   for executors that apply the epilogue as XLA ops;
                   in-kernel epilogues declare their own)
  execute(...)     run the spec under a launch config, epilogue
                   included (in-kernel when ``fuses_epilogue``, XLA
                   ops otherwise)

The built-in menu: ``lax`` (XLA's conv: grouped specs, the stems, every
spec off the TPU, and the tests' reference), ``cuconv_pallas`` (the
fused tap-accumulation kernel), ``winograd_pallas`` (large 3x3 stride-1),
``cuconv_int8`` (the int8 path) and ``depthwise_tap``.

``convspec.plan()`` is pure negotiation over these declarations
(forced > measured cache > heuristic claims > cheapest supported);
nothing outside this module special-cases an executor name.  Adding a
kernel — in-tree or third-party — is one ``register(MyExecutor())``
call, not a planner edit (README "Registering a third-party executor").
"""
from __future__ import annotations

import dataclasses
from collections.abc import Mapping as _MappingABC
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.convspec import EPILOGUES

# VMEM working-set budget for the fused Pallas kernel (per-core VMEM is
# ~16 MB; leave headroom for Mosaic's own buffers).  Read at supports()
# time so tests and deployments can adjust it.
FUSED_VMEM_BUDGET = 12 * 1024 * 1024

# cost-model exchange rate: abstract cost units per byte of extra HBM
# traffic (a memory-bound conv does O(10) MACs per byte at the balance
# point; the exact number only has to rank executors, not predict time)
_COST_PER_HBM_BYTE = 8.0


def _is_small(spec) -> bool:
    """The paper's small-batch/small-spatial region (figs 5-7)."""
    n, h = spec.in_shape[0], spec.in_shape[1]
    return n == 1 or (h <= 14 and n <= 16)


# ---------------------------------------------------------------------------
# launch configurations (DESIGN.md §9)

@dataclasses.dataclass(frozen=True)
class LaunchConfig:
    """One launch configuration: named integer kernel-geometry dims.

    Immutable and hashable (it rides inside frozen ``ConvPlan``s) and
    JSON-round-trippable via ``as_dict`` (the persisted autotune cache).
    An *empty* config (the untunable executors' only candidate) is
    falsy, so callers can write ``if plan.config: ...``.
    """
    dims: Tuple[Tuple[str, int], ...] = ()

    @classmethod
    def of(cls, value) -> "LaunchConfig":
        """Coerce any accepted spelling (LaunchConfig | mapping of
        str -> int | None) into a LaunchConfig."""
        if value is None:
            return cls()
        if isinstance(value, cls):
            return value
        if isinstance(value, _MappingABC):
            try:
                dims = tuple(sorted((str(k), int(v))
                                    for k, v in value.items()))
            except (TypeError, ValueError) as e:
                raise ValueError(f"launch-config dims must be str -> int; "
                                 f"got {dict(value)!r}") from e
            return cls(dims)
        raise ValueError(f"cannot build a LaunchConfig from {value!r}")

    def as_dict(self) -> Dict[str, int]:
        return dict(self.dims)

    def get(self, name: str, default: Optional[int] = None) -> Optional[int]:
        for k, v in self.dims:
            if k == name:
                return v
        return default

    def __getitem__(self, name: str) -> int:
        v = self.get(name)
        if v is None:
            raise KeyError(name)
        return v

    def __contains__(self, name: str) -> bool:
        return self.get(name) is not None

    def __bool__(self) -> bool:
        return bool(self.dims)

    def key(self) -> str:
        """Stable one-token rendering for explain()/benchmark rows."""
        return ",".join(f"{k}={v}" for k, v in self.dims) or "-"


# TPU block alignment: a block's last dim must be a multiple of 128
# lanes and its second-last a multiple of 8 sublanes, unless the block
# spans the whole array dim.  These tile dims land on a lane axis of
# some kernel block; the other tiled dim (tp) lands on a sublane axis.
_LANE_DIMS = ("tm", "tc")


def _dedup_configs(dicts: Iterable[Dict[str, int]],
                   extents: Optional[Dict[str, int]] = None
                   ) -> Tuple[LaunchConfig, ...]:
    """Ordered, deduplicated candidate list (clamped candidates often
    collapse on small paper shapes — e.g. every tp > N*OH*OW).

    ``extents`` maps tile dims to the array extent they tile; a
    candidate whose tile is neither the whole extent nor TPU-aligned
    (a multiple of 128 for ``_LANE_DIMS``, of 8 for the others)
    is dropped, so neither the model-chosen default nor a measured
    sweep picks a block shape the TPU compiler refuses.
    """
    out, seen = [], set()
    for d in dicts:
        if extents and any(
                d[k] < n and d[k] % (128 if k in _LANE_DIMS else 8)
                for k, n in extents.items()):
            continue
        c = LaunchConfig.of(d)
        if c.dims not in seen:
            seen.add(c.dims)
            out.append(c)
    return tuple(out)


class Executor:
    """One registered convolution algorithm: capabilities + execution.

    Subclasses override the declarations; the planner only ever talks to
    these methods, so a third-party executor participates in forced
    resolution, measured autotuning, heuristic negotiation and the
    cheapest-supported tier with zero planner changes.
    """

    #: registry identity (also the persisted-cache algorithm string)
    name: str = ""
    #: ConvSpec.dtype strings this executor accepts
    dtypes: Tuple[str, ...] = ("float32", "bfloat16")
    #: accumulation behavior for the channel contraction
    accum: str = "float32"
    #: can execute groups > 1 specs exactly
    supports_groups: bool = False
    #: the bias/activation epilogue runs inside the kernel (no extra
    #: HBM trip)
    fuses_epilogue: bool = False
    #: ConvSpec.epilogue values this executor runs
    epilogues: Tuple[str, ...] = EPILOGUES
    #: forward the planner's interpret flag (Pallas executors)
    takes_interpret: bool = False
    #: names of the launch-config dims this executor can tune; () means
    #: untunable (library/XLA executors — one empty config, nothing to
    #: sweep)
    tunable: Tuple[str, ...] = ()

    # -- capability ------------------------------------------------------
    def supports(self, spec) -> Tuple[bool, str]:
        """Can this executor run ``spec`` exactly (ignoring speed)?

        Common gates (dtype, groups) live here; geometry-specific limits
        go in ``_supports``.
        """
        if spec.dtype not in self.dtypes:
            return False, (f"dtype {spec.dtype} not in {self.name}'s "
                           f"declared dtypes {self.dtypes}")
        if spec.groups != 1 and not self.supports_groups:
            return False, (f"no grouped-conv support (groups={spec.groups}); "
                           f"lax feature_group_count is the executor")
        if spec.epilogue not in self.epilogues:
            return False, (f"epilogue {spec.epilogue!r} not in {self.name}'s "
                           f"declared epilogues {self.epilogues}")
        fusable = self.fusions(spec)
        if spec.fused_add != "none" and "add" not in fusable:
            return False, (f"{self.name} does not fuse a residual add "
                           f"(declared fusions for this spec: "
                           f"{list(fusable) or 'none'})")
        if spec.fused_pool and "pool" not in fusable:
            return False, (f"{self.name} does not fuse pool "
                           f"{spec.fused_pool!r} (declared fusions for "
                           f"this spec: {list(fusable) or 'none'})")
        return self._supports(spec)

    def _supports(self, spec) -> Tuple[bool, str]:
        return True, "generic algorithm"

    def fusions(self, spec) -> Tuple[str, ...]:
        """Cross-layer fusions ("add", "pool") this executor can absorb
        for ``spec``'s geometry (DESIGN.md §10).

        Non-fusing executors take every fusion for free: ``execute``
        applies the residual add / pool as XLA ops after the bare conv,
        exactly as the unfused graph would have — so folding nodes into
        their specs is always numerically safe.  In-kernel
        (``fuses_epilogue``) executors must opt in per fusion kind and
        handle the operands inside ``_execute``.
        """
        if self.fuses_epilogue:
            return ()
        return ("add", "pool")

    # -- tuning space (DESIGN.md §9) -------------------------------------
    def configs(self, spec) -> Tuple[LaunchConfig, ...]:
        """Ordered candidate launch configs for ``spec``.

        Candidate 0 is the historical hard-coded geometry (the safe
        default the kernel shipped with); candidates are clamped to the
        spec's dims but NOT yet feasibility-pruned — pair with
        ``config_supports`` (the measured sweep and ``default_config``
        both do).  Untunable executors expose one empty config.
        """
        return (LaunchConfig(),)

    def config_supports(self, spec, config) -> Tuple[bool, str]:
        """Can this executor run ``spec`` under ``config`` exactly?

        Common gates (declared tunable dims, positive values, the VMEM
        budget via ``vmem_bytes``) live here; geometry-specific rules go
        in ``_config_supports``.
        """
        config = LaunchConfig.of(config)
        unknown = [k for k, _ in config.dims if k not in self.tunable]
        if unknown:
            return False, (f"{self.name} has no tunable dim(s) {unknown} "
                           f"(tunable: {list(self.tunable) or 'none'})")
        bad = [(k, v) for k, v in config.dims if v < 1]
        if bad:
            return False, f"launch dims must be >= 1; got {bad}"
        ok, why = self._config_supports(spec, config)
        if not ok:
            return False, why
        need = self.vmem_bytes(spec, config)
        if need is not None and need > FUSED_VMEM_BUDGET:
            return False, (f"config [{config.key()}] working set "
                           f"{need / 2**20:.1f} MB > "
                           f"{FUSED_VMEM_BUDGET / 2**20:.0f} MB VMEM budget")
        return True, why

    def _config_supports(self, spec, config) -> Tuple[bool, str]:
        return True, "config geometry ok"

    def config_cost(self, spec, config) -> float:
        """Abstract cost of running ``spec`` under ``config`` — only has
        to *rank* candidates (``default_config`` minimizes it; ties keep
        the earliest candidate), so a number or a tuple compared in
        order.  Tunable executors model grid-step count (bigger feasible
        blocks = fewer steps = fuller MXU)."""
        return 0.0

    def padded_share(self, spec, config) -> Optional[float]:
        """Share of the output elements the kernel computes under
        ``config`` that lie beyond the real output (blocks rounded up
        past ``OH`` rows or ``M`` lanes; 0 when the blocks tile it
        exactly), or None when the executor has no block model."""
        return None

    def default_config(self, spec) -> LaunchConfig:
        """Model-chosen launch config absent measurement: the cheapest
        VMEM-feasible candidate by ``config_cost`` (stable min — ties
        keep candidate 0, the historical geometry)."""
        cands = self.configs(spec)
        feasible = [c for c in cands if self.config_supports(spec, c)[0]]
        if not feasible:
            return cands[0]
        return min(feasible, key=lambda c: self.config_cost(spec, c))

    # -- negotiation inputs ----------------------------------------------
    def heuristic_claim(self, spec, backend: str
                        ) -> Optional[Tuple[int, str]]:
        """``(score, reason)`` claim on the paper's regions, or None.

        Only consulted when ``supports(spec)`` holds; the highest score
        among supporting executors wins the heuristic tier.
        """
        return None

    def cost(self, spec) -> float:
        """Abstract cost for the cheapest-supported tier: the executor's
        arithmetic (``flop_cost``) plus its extra HBM traffic, weighted
        by ``_COST_PER_HBM_BYTE``."""
        return (self.flop_cost(spec)
                + _COST_PER_HBM_BYTE * self.extra_hbm_bytes(spec))

    def flop_cost(self, spec) -> float:
        """Arithmetic term: direct-conv MACs (identical for every exact
        executor; transform-based executors override)."""
        n, oh, ow, m = spec.out_shape
        kh, kw, cpg, _ = spec.filter_shape
        return 2.0 * n * oh * ow * m * kh * kw * cpg

    def extra_hbm_bytes(self, spec) -> float:
        """HBM traffic beyond reading inputs and writing the output
        once (materialized temporaries, transform tensors, ...)."""
        return 0.0

    def vmem_bytes(self, spec, config=None) -> Optional[int]:
        """Static VMEM working-set estimate under ``config`` (None: the
        default hard-coded geometry), or None when there is no VMEM
        model.  ``config_supports`` prunes candidates through this
        before any measurement happens."""
        return None

    def fallback(self, spec) -> Tuple[str, str]:
        """Closest registered stand-in when this executor is forced but
        cannot run ``spec`` (grouped specs raise instead; see plan())."""
        return "lax", "library conv covers all geometries"

    # -- execution -------------------------------------------------------
    def execute(self, spec, x, w, bias=None, addend=None, interpret=None,
                config=None, quant=None):
        """Run ``spec`` on ``(x, w, bias[, addend])``, epilogue included.

        Operands are cast to the spec dtype first (under a bf16
        precision policy the master weights stay fp32); the contraction
        accumulates per ``accum``.  Non-fusing executors apply the
        bias/ReLU epilogue — and any cross-layer fusion the spec
        carries (residual ``addend``, trailing pool) — as XLA ops after
        the bare conv; ``fuses_epilogue`` executors absorb everything
        in-kernel.  ``config`` is the plan's resolved launch config.
        ``quant`` is the quantization payload (calibrated
        activation scale) ConvPlan forwards on int8 plans — ignored
        here; int8-declaring executors override ``execute`` and consume
        it.
        """
        dtype = jnp.dtype(spec.dtype)
        x = x if x.dtype == dtype else x.astype(dtype)
        w = w if w.dtype == dtype else w.astype(dtype)
        if bias is not None and bias.dtype != dtype:
            bias = bias.astype(dtype)
        if spec.fused_add != "none" and addend is None:
            raise ValueError(f"fused-add spec {spec.key()} needs an addend")
        if addend is not None and addend.dtype != dtype:
            addend = addend.astype(dtype)
        config = LaunchConfig.of(config)
        if not self.fuses_epilogue:
            y = self._execute(spec, x, w, bias, interpret, config=config)
            return _xla_epilogue(spec, y, bias, addend)
        # only executors declaring the "add" fusion are handed an addend
        kwargs = {} if addend is None else {"addend": addend}
        return self._execute(spec, x, w, bias, interpret, config=config,
                             **kwargs)

    def _execute(self, spec, x, w, bias, interpret, config=None):
        """The conv itself under ``config``; ``fuses_epilogue``
        executors also take ``addend=`` and apply the whole epilogue.
        Every executor overrides this (``register`` checks)."""
        raise NotImplementedError

    def __repr__(self):
        return (f"<Executor {self.name} dtypes={self.dtypes} "
                f"accum={self.accum} groups={self.supports_groups} "
                f"fused_epilogue={self.fuses_epilogue}>")


def _xla_epilogue(spec, y, bias, addend):
    """``spec``'s epilogue as XLA ops after a bare conv: bias, residual
    addend, activation (after the add under ``add_relu``), pool."""
    if spec.has_bias:
        y = y + bias
    if spec.fused_add != "none":
        y = y + addend
        if spec.fused_add == "add_relu":
            y = jnp.maximum(y, 0)
    elif spec.activation == "relu":
        y = jnp.maximum(y, 0)
    elif spec.activation == "gelu":
        y = jax.nn.gelu(y, approximate=False)
    if spec.fused_pool:
        from repro.kernels import ops
        kind, pkh, pkw, psh, psw, pph, ppw = spec.fused_pool
        y = ops.pool2d(y, kind=kind, window=(pkh, pkw),
                       stride=(psh, psw), padding=(pph, ppw))
    return y


# ---------------------------------------------------------------------------
# registry

_REGISTRY: Dict[str, Executor] = {}


def register(executor: Executor) -> Executor:
    """Add an executor to the menu (third-party entry point).

    The name becomes resolvable everywhere at once: ``conv2d``'s
    ``algorithm=`` strings, forced plans, measured autotuning, heuristic
    negotiation and persisted cache entries.
    """
    name = executor.name
    if not name or not isinstance(name, str):
        raise ValueError(f"executor needs a non-empty string name; "
                         f"got {name!r}")
    if name in _REGISTRY:
        raise ValueError(f"executor {name!r} already registered; "
                         f"unregister it first to replace it")
    if type(executor)._execute is Executor._execute:
        # fail at registration, not deep inside a jitted trace
        raise ValueError(f"executor {name!r} must override `_execute`")
    _REGISTRY[name] = executor
    return executor


def unregister(name: str) -> Executor:
    """Remove a registered executor (returns it); unknown names raise."""
    ex = _REGISTRY.pop(name, None)
    if ex is None:
        raise KeyError(f"unknown algorithm {name!r}; "
                       f"registered: {sorted(_REGISTRY)}")
    return ex


def get(name: str) -> Executor:
    ex = _REGISTRY.get(name)
    if ex is None:
        raise KeyError(f"unknown algorithm {name!r}; "
                       f"registered: {sorted(_REGISTRY)}")
    return ex


def capable(name: str, spec) -> bool:
    """Is ``name`` a registered executor whose declarations cover
    ``spec``?  The one rule every stale-cache reader applies: persisted
    entries (measured winners, graph plans) naming unregistered or
    no-longer-capable executors must be dropped, never served."""
    ex = _REGISTRY.get(name)
    return ex is not None and ex.supports(spec)[0]


def names() -> Tuple[str, ...]:
    """Registered executor names, in registration order."""
    return tuple(_REGISTRY)


def registered() -> Dict[str, Executor]:
    """Snapshot of the registry (mutating it does not unregister)."""
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# negotiation

def negotiate(spec, backend: str) -> Tuple[str, str, str]:
    """Pick an executor for ``spec`` from capability declarations alone.

    Returns ``(name, source, reason)``: the highest-scoring heuristic
    claim among supporting executors (``source="heuristic"``, the
    paper's regions), else the cheapest supported executor by cost model
    (``source="cost"``).  No executor supporting the spec at all is an
    error that names every executor's refusal — the signal a precision
    policy or spec asks for something the menu cannot serve.
    """
    best_claim = None          # (score, name, reason); first-registered wins ties
    cheapest = None            # (cost, name)
    refusals = []
    for ex in _REGISTRY.values():
        ok, why = ex.supports(spec)
        if not ok:
            refusals.append(f"{ex.name}: {why}")
            continue
        claim = ex.heuristic_claim(spec, backend)
        if claim is not None and (best_claim is None
                                  or claim[0] > best_claim[0]):
            best_claim = (claim[0], ex.name, claim[1])
        c = ex.cost(spec)
        if cheapest is None or c < cheapest[0]:
            cheapest = (c, ex.name)
    if best_claim is not None:
        return best_claim[1], "heuristic", best_claim[2]
    if cheapest is not None:
        return (cheapest[1], "cost",
                f"cheapest supported executor (cost {cheapest[0]:.3g})")
    raise ValueError(
        f"no registered executor supports spec {spec.key()}; "
        + "; ".join(refusals))


def supporting(spec) -> Tuple[str, ...]:
    """Names of every registered executor that can run ``spec`` exactly
    (the measured autotuner's default candidate set)."""
    return tuple(n for n, ex in _REGISTRY.items() if ex.supports(spec)[0])


# ---------------------------------------------------------------------------
# built-in executors (the paper's algorithm family)

class LaxExecutor(Executor):
    """XLA's native convolution — the cuDNN stand-in of the paper's
    comparison, and the executor for grouped specs (depthwise ones may
    go to ``depthwise_tap``)."""
    name = "lax"
    supports_groups = True

    def _supports(self, spec):
        if spec.groups != 1:
            return True, (f"grouped conv (groups={spec.groups}): library "
                          f"feature_group_count")
        return True, "library conv covers all geometries"

    def heuristic_claim(self, spec, backend):
        if spec.groups != 1:
            return 95, (f"grouped conv (groups={spec.groups}): library "
                        f"feature_group_count")
        if backend != "tpu":
            # the Pallas kernels would run in interpret mode
            return 40, "off TPU: library conv"
        if not spec.unit_stride:
            # a low claim: any capable kernel claiming the strided
            # region outranks it, so winning here means nothing else did
            return 40, "strided conv: library kernel (no higher-priority claim)"
        return None

    def _execute(self, spec, x, w, bias, interpret, config=None):
        from repro.core import cuconv
        return cuconv.conv_lax(x, w, stride=spec.stride,
                               padding=spec.padding, groups=spec.groups)


# Tiled-GEMM launch candidates of the int8 GEMM kernel: (tp, tm, tc) =
# pixel / out-channel / contraction tiles.
# Candidate 0 is the historical hard-coded geometry; the rest widen or
# shrink each axis (clamped per spec, so small paper shapes dedupe).
_GEMM_TILES = (
    (256, 128, 512),
    (512, 256, 512),
    (256, 512, 512),
    (128, 128, 256),
    (512, 128, 1024),
    (128, 64, 128),
)


def _gemm_tile_configs(p: int, m: int, c: int) -> Tuple[LaunchConfig, ...]:
    return _dedup_configs(
        ({"tp": min(tp, p), "tm": min(tm, m), "tc": min(tc, c)}
         for tp, tm, tc in _GEMM_TILES),
        {"tp": p, "tm": m, "tc": c})


def _gemm_tile_vmem(config: LaunchConfig, itemsize: int) -> int:
    """Live-block model of one tiled GEMM step at the TPU's tiled layout:
    x/w input blocks and the (at most 32-bit) output block double
    buffered, plus the 32-bit VMEM accumulator."""
    from repro.kernels._compat import tiled_bytes
    tp = config.get("tp", 256)
    tm = config.get("tm", 128)
    tc = config.get("tc", 512)
    return (2 * (tiled_bytes((tp, tc), itemsize)
                 + tiled_bytes((tc, tm), itemsize)
                 + tiled_bytes((tp, tm), 4))
            + tiled_bytes((tp, tm), 4))


def _gemm_tile_steps(p: int, m: int, c: int, config: LaunchConfig) -> float:
    """Grid-step count of the tiled GEMM under ``config`` (the ranking
    ``config_cost`` minimizes)."""
    tp = min(config.get("tp", 256), p)
    tm = min(config.get("tm", 128), m)
    tc = min(config.get("tc", 512), c)
    return (-(-p // tp)) * (-(-m // tm)) * (-(-c // tc))


def _exact_rows(oh: int, target: int) -> int:
    """The divisor of ``oh`` nearest ``target`` output rows a step (ties
    to the larger), so the row blocks tile the output exactly; the
    padded ``min(target, oh)`` where that divisor is below
    ``target / 2`` (a prime ``OH`` keeps its padded block rather than
    falling to one row a step)."""
    d = min((d for d in range(1, oh + 1) if oh % d == 0),
            key=lambda d: (abs(d - target), -d))
    return d if 2 * d >= target else min(target, oh)


def _exact_tm(m: int, target: int) -> int:
    """The largest multiple of 128 lanes that divides ``m`` and is at
    most ``target``; ``m`` itself (one whole-extent tile) where none
    does."""
    for tm in range(target - target % 128, 0, -128):
        if m % tm == 0:
            return tm
    return m


class FusedPallasExecutor(Executor):
    """The fused Pallas TPU kernel: any stride >= 1, per-tap partials
    accumulated in VMEM, bias + ReLU/GELU epilogue fused before the
    single HBM write.

    Tuning space: ``tm`` (output-channel tile) x ``rows`` (output rows
    per grid step — the multi-row blocking that lets short-``OW`` paper
    shapes feed the MXU a (rows*OW x C) window instead of one row).
    ``rows <= OH`` is a ``config_supports`` rule, so stale persisted
    configs from an earlier geometry are re-resolved, never served.
    Candidates tile the output exactly (``rows | OH``, ``tm | M``)
    wherever the geometry allows: a padded block computes dead rows or
    lanes, and XLA pads its inputs and slices its output around the
    kernel.
    """
    name = "cuconv_pallas"
    fuses_epilogue = True
    takes_interpret = True
    tunable = ("tm", "rows")

    @staticmethod
    def _pool3(spec):
        """``(kind, psh, psw)`` kernel-pool tuple for a fused-pool spec."""
        kind, _, _, psh, psw, _, _ = spec.fused_pool
        return (kind, psh, psw)

    def fusions(self, spec):
        """In-kernel fusions: any residual add; non-overlapping unpadded
        pools whose geometry the multi-row blocking can cover (window ==
        stride, OH/OW divisible by the pool stride)."""
        out = ("add",)
        if spec.fused_pool:
            kind, pkh, pkw, psh, psw, pph, ppw = spec.fused_pool
            _, oh, ow, _ = spec.out_shape
            if ((pkh, pkw) == (psh, psw) and (pph, ppw) == (0, 0)
                    and oh % psh == 0 and ow % psw == 0):
                out = out + ("pool",)
        return out

    def vmem_bytes(self, spec, config=None):
        from repro.kernels.cuconv_fused import vmem_bytes
        cfg = LaunchConfig.of(config)
        itemsize = jnp.dtype(spec.dtype).itemsize
        return vmem_bytes(spec.in_shape, spec.filter_shape,
                          tm=cfg.get("tm", 128), rows=cfg.get("rows", 1),
                          pad=spec.padding, stride=spec.stride,
                          itemsize=itemsize,
                          addend=spec.fused_add != "none",
                          pool=(self._pool3(spec) if spec.fused_pool
                                else None))

    def _supports(self, spec):
        need = self.vmem_bytes(spec)
        if need > FUSED_VMEM_BUDGET:
            return False, (f"fused working set {need / 2**20:.1f} MB "
                           f"> {FUSED_VMEM_BUDGET / 2**20:.0f} MB "
                           f"VMEM budget")
        if spec.fused_pool and not any(
                self.config_supports(spec, c)[0] for c in self.configs(spec)):
            return False, ("no feasible multi-row blocking covers fused "
                           f"pool {spec.fused_pool!r}")
        return True, "fused Pallas kernel fits VMEM"

    def configs(self, spec):
        _, oh, _, m = spec.out_shape
        if spec.fused_pool:
            # rows must tile both the pool stride and OH (candidate 0:
            # one pool window of output rows per grid step)
            psh = spec.fused_pool[3]
            rows_cands = tuple(r for r in (psh, 2 * psh, 4 * psh, 8 * psh)
                               if r <= oh) or (psh,)
        else:
            # each row target's exact tiling of OH, then the target
            # itself (a measured sweep may still time a padded block;
            # config_cost ranks it below every exact one)
            rows_cands = tuple(r for target in (1, 2, 4, 8)
                               for r in (_exact_rows(oh, target), target))
        # each lane target, clamped to M, then its exact tiling of M
        tms = [t for target in (128, 256, 512)
               for t in (min(target, m), _exact_tm(m, target))]
        return _dedup_configs(
            ({"tm": tm, "rows": min(rows, oh)}
             for tm in tms                     # candidate 0: tm=128, rows=1
             for rows in rows_cands),
            {"tm": m})

    def _config_supports(self, spec, config):
        from repro.kernels._compat import STRIDED_LANES
        rows = config.get("rows", 1)
        _, oh, _, m = spec.out_shape
        if rows > oh:
            return False, (f"rows={rows} exceeds OH={oh} for "
                           f"{spec.key()}")
        if spec.fused_pool:
            psh = spec.fused_pool[3]
            if rows % psh:
                return False, (f"fused pool needs rows % pool stride == 0; "
                               f"got rows={rows}, psh={psh}")
            if oh % rows:
                return False, (f"fused pool needs OH % rows == 0; "
                               f"got OH={oh}, rows={rows}")
            tm = min(config.get("tm", 128), m)
            if tm > STRIDED_LANES:
                return False, (f"fused pool reads its scratch with strided "
                               f"loads, which Mosaic takes on at most "
                               f"{STRIDED_LANES} lanes; got tm={tm}")
        return True, "config geometry ok"

    def config_cost(self, spec, config):
        """``(computed, steps)``: the output rows x lanes the kernel
        computes over all taps (``OHB*rows`` by ``ceil(M/tm)*tm``, so a
        padded block pays for its dead rows and lanes and never ties an
        exact one), then the grid steps (bigger exact blocks win)."""
        n, oh, _, m = spec.out_shape
        kh, kw = spec.filter_shape[:2]
        tm = min(config.get("tm", 128), m)
        rows = max(1, min(config.get("rows", 1), oh))
        steps = n * (-(-oh // rows)) * (-(-m // tm)) * kh * kw
        return steps * rows * tm, steps

    def padded_share(self, spec, config):
        n, oh, _, m = spec.out_shape
        kh, kw = spec.filter_shape[:2]
        computed, _ = self.config_cost(spec, LaunchConfig.of(config))
        return 1.0 - n * oh * m * kh * kw / computed

    def heuristic_claim(self, spec, backend):
        if backend != "tpu":
            return None                    # interpret mode elsewhere
        if spec.has_fusion:
            # outranks every per-layer claim: the folded add/pool stays
            # resident in VMEM instead of round-tripping HBM
            return 85, "cross-layer fusion resident in VMEM"
        if not spec.unit_stride:
            if spec.in_shape[3] < 8:
                # an RGB stem: each tap's GEMM contracts over C rows and
                # its phase-split input pads C to 128 lanes (ConvNeXt-T's
                # 4x4/4 stem took 3.2 ms a 32-image batch; PERF.md)
                return None
            return 80, "strided conv: fused kernel on TPU"
        if spec.is_1x1:
            return 80, "1x1: fused GEMM + epilogue in VMEM"
        if _is_small(spec):
            return 80, "small batch/spatial: cuConv region"
        return None

    def _execute(self, spec, x, w, bias, interpret, config=None,
                 addend=None):
        # epilogue fused into the kernel: the accumulator takes
        # bias + residual addend + activation (or the fused pool) in
        # VMEM before its single HBM write
        from repro.kernels import ops
        cfg = LaunchConfig.of(config)
        if spec.fused_add != "none":
            # post-add activation
            act = "relu" if spec.fused_add == "add_relu" else None
        else:
            act = spec.activation
        return ops.cuconv_fused(
            x, w, spec.padding, stride=spec.stride,
            bias=bias if spec.has_bias else None,
            activation=act,
            addend=addend,
            pool=self._pool3(spec) if spec.fused_pool else None,
            tm=cfg.get("tm", 128), rows=cfg.get("rows", 1),
            interpret=interpret)


# Winograd-Pallas launch candidates: (tiles, tm, tc) triples tried
# under both F(m,3) variants, ``tiles`` the Winograd tiles one grid
# step should hold (the executor turns it into ``rows``, whole tile
# rows per step, for the spec's tile-row width).  Candidate 0 under
# m=2 is the kernel's shipped default geometry; the smaller tile counts
# keep the F(4,3) domain (36 positions vs 16) inside the VMEM budget on
# big-channel specs, with the channel tiles still 128-lane aligned.
# ``tm`` stays at the kernel's strided-store limit (128 lanes).
_WINO_TILES = (
    (128, 128, 128),
    (256, 128, 128),
    (128, 128, 256),
    (64, 128, 128),
    (32, 128, 128),
)


def _wino_rows(th: int, tw: int, tiles: int) -> int:
    """Tile rows per step nearest ``tiles`` tiles of ``tw`` columns.
    Below an image's ``th`` tile rows they are spread evenly over the
    bands (so the last band is as full as the rest: 28 tile rows at 8
    per step run as 4 bands of 7); from ``th`` up a step takes whole
    images (``winograd_pallas.geometry``)."""
    rows = max(1, tiles // tw)
    if rows >= th:
        return rows
    bands = -(-th // rows)
    return -(-th // bands)


class WinogradPallasExecutor(Executor):
    """Tiled Pallas Winograd F(m,3): the whole Winograd domain —
    tile gather, B^T d B transform, per-position channel GEMMs, fp32
    accumulator, A^T m A inverse, bias/ReLU/residual epilogue — lives
    in VMEM inside one kernel (kernels/winograd_pallas.py); the pure-jnp
    ``core.winograd.conv_winograd`` is the reference for its transforms.

    Tuning space: ``m`` (the F(m,3) variant — F(2x2,3x3) with 16 tile
    positions and 2.25x multiply savings, or F(4x4,3x3) with 36
    positions and 4x savings at looser numerics), ``rows`` (tile rows
    per grid step), ``tm``/``tc`` (output/input channel tiles).  The
    variant is a *config dim*, so ``tune="full"`` arbitrates F(2,3) vs
    F(4,3) per spec and the winner persists like any other launch
    config.
    """
    name = "winograd_pallas"
    fuses_epilogue = True
    takes_interpret = True
    tunable = ("m", "rows", "tm", "tc")
    epilogues = ("none", "bias", "relu", "bias_relu")

    def fusions(self, spec):
        # the residual add folds into the in-kernel epilogue (the
        # addend rides the output-tile layout); pool does not
        return ("add",)

    def _supports(self, spec):
        if spec.filter_shape[:2] != (3, 3) or not spec.unit_stride:
            return False, "Winograd F(m,3) needs 3x3 stride-1"
        if not any(self.config_supports(spec, c)[0]
                   for c in self.configs(spec)):
            return False, ("no Winograd tile candidate fits the VMEM "
                           "budget for this spec")
        return True, "3x3 stride-1: tiled Pallas Winograd"

    def _geometry(self, spec, fm, rows):
        """The kernel's ``geometry`` of ``spec`` under F(fm,3), ``rows``."""
        from repro.kernels.winograd_pallas import geometry
        n, h, w, _ = spec.in_shape
        return geometry(n, h, w, spec.padding, fm, rows,
                        jnp.dtype(spec.dtype).itemsize)

    def _step_rows(self, spec, fm, tiles):
        """``rows`` for about ``tiles`` tiles a step, as the kernel runs
        it (images per step times tile rows of each)."""
        from repro.kernels.winograd_pallas import tile_grid
        _, h, w, _ = spec.in_shape
        th, twp = tile_grid(h, w, spec.padding, fm,
                            jnp.dtype(spec.dtype).itemsize)
        _, _, nb, rows, *_ = self._geometry(spec, fm,
                                            _wino_rows(th, twp, tiles))
        return nb * rows

    def configs(self, spec):
        _, _, _, m = spec.out_shape
        c = spec.filter_shape[2]
        cands = ()
        for fm in (2, 4):
            cands += _dedup_configs(
                ({"m": fm, "rows": self._step_rows(spec, fm, tiles),
                  "tm": min(tm, m), "tc": min(tc, c)}
                 for tiles, tm, tc in _WINO_TILES),
                {"tm": m, "tc": c})
        return cands

    def _config_supports(self, spec, config):
        from repro.kernels._compat import STRIDED_LANES
        fm = config.get("m", 2)
        if fm not in (2, 4):
            return False, (f"F(m,3) variant must be m=2 or m=4; "
                           f"got m={fm}")
        tm = min(config.get("tm", 128), spec.out_shape[3])
        if tm > STRIDED_LANES:
            return False, (f"the NHWC output store is a strided store, "
                           f"which Mosaic takes on at most "
                           f"{STRIDED_LANES} lanes; got tm={tm}")
        return True, "config geometry ok"

    def vmem_bytes(self, spec, config=None):
        from repro.kernels.winograd_pallas import vmem_bytes
        cfg = LaunchConfig.of(config)
        return vmem_bytes(spec.in_shape, spec.filter_shape,
                          m=cfg.get("m", 2), rows=cfg.get("rows", 4),
                          tm=cfg.get("tm", 128), tc=cfg.get("tc", 128),
                          itemsize=jnp.dtype(spec.dtype).itemsize,
                          bias=spec.has_bias,
                          addend=spec.fused_add != "none",
                          padding=spec.padding)

    def config_cost(self, spec, config):
        fm = config.get("m", 2)
        n, _, _, m = spec.out_shape
        c = spec.filter_shape[2]
        _, _, nb, _, bands, *_ = self._geometry(spec, fm,
                                                config.get("rows", 4))
        tm = min(config.get("tm", 128), m)
        tc = min(config.get("tc", 128), c)
        steps = n // nb * bands * (-(-m // tm)) * (-(-c // tc))
        # (m+2)^2 per-position GEMMs per step: F(4,3) quarters the tile
        # count but grows the position count 16 -> 36, netting ~0.56x —
        # the model prefers it wherever it stays VMEM-feasible
        return steps * (fm + 2) ** 2

    def flop_cost(self, spec):
        # 2.25x fewer multiplies than direct under the conservative
        # F(2,3) variant (F(4,3), when tuned in, saves 4x)
        return super().flop_cost(spec) / 2.25

    def extra_hbm_bytes(self, spec):
        n, h, w, c = spec.in_shape
        m = spec.filter_shape[3]
        ph, pw = spec.padding
        itemsize = jnp.dtype(spec.dtype).itemsize
        # the padded, phase-split input (written, then read by the
        # kernel) at the spec dtype and the transformed filters (f32,
        # small and reused); the tiles, the Winograd-domain tensors and
        # the output tiles never leave VMEM (the point of the kernel)
        return (2.0 * n * (h + 2 * ph) * (w + 2 * pw) * c * itemsize
                + 2.0 * 16 * c * m * 4)

    def heuristic_claim(self, spec, backend):
        if backend != "tpu" or spec.has_fusion:
            return None
        if not _is_small(spec):
            return 82, "large 3x3: tiled Pallas Winograd (fig. 6 region)"
        return None

    def _execute(self, spec, x, w, bias, interpret, config=None,
                 addend=None):
        from repro.kernels import ops
        cfg = LaunchConfig.of(config)
        if spec.fused_add != "none":
            relu = spec.fused_add == "add_relu"    # post-add activation
        else:
            relu = spec.wants_relu
        return ops.winograd_fused(
            x, w, spec.padding,
            bias=bias if spec.has_bias else None,
            activation="relu" if relu else None,
            addend=addend, m=cfg.get("m", 2), rows=cfg.get("rows", 4),
            tm=cfg.get("tm", 128), tc=cfg.get("tc", 128),
            interpret=interpret)


class Int8PallasExecutor(Executor):
    """Int8 inference executor: symmetric quantization in, int8 x int8
    -> **int32** accumulation on the MXU integer path, fp32
    requantization in the epilogue (DESIGN.md §13).

    The only executor declaring ``dtypes=("int8",)`` — the quantize
    pass flips eligible conv specs to int8 and negotiation lands here;
    every cache key (autotune configs, graph signatures) is
    dtype-distinct by construction, so int8 tuning never collides with
    the fp plans of the same geometry.

    Scales: weights get **per-output-channel** symmetric scales computed
    from the weight values in-trace (exact, no calibration needed);
    activations use the **per-tensor** calibrated scale riding in the
    plan's ``quant`` payload, falling back to a dynamic in-trace
    ``max|x|/127`` when none rode in (autotune timing, ad-hoc plans).
    Epilogue order: dequantize the int32 accumulator through
    ``x_scale * w_scale[m]``, then bias + residual + activation + pool
    at fp32 — identical shapes and operand dtypes to the fp executors,
    so quantized nodes drop into any graph position.

    Tuning space: the tiled-GEMM tiles over the im2col dims
    (N*OH*OW, M, KH*KW*C); int8 tiles are a quarter the bytes of f32,
    so bigger blocks stay VMEM-feasible — the throughput lever the
    ROADMAP's int8 item names.
    """
    name = "cuconv_int8"
    dtypes = ("int8",)
    accum = "int32"
    takes_interpret = True
    tunable = ("tp", "tm", "tc")

    def _supports(self, spec):
        return True, "int8 im2col GEMM, int32 accumulation"

    def heuristic_claim(self, spec, backend):
        if backend == "tpu":
            return 95, "int8: quantized GEMM on the MXU integer path"
        return None

    def extra_hbm_bytes(self, spec):
        # the materialized int8 patch matrix (1 byte/elem)
        n, oh, ow, _ = spec.out_shape
        kh, kw, c, _ = spec.filter_shape
        return float(n * oh * ow * kh * kw * c)

    def _gemm_dims(self, spec):
        n, oh, ow, m = spec.out_shape
        kh, kw, c, _ = spec.filter_shape
        return n * oh * ow, m, kh * kw * c

    def configs(self, spec):
        return _gemm_tile_configs(*self._gemm_dims(spec))

    def vmem_bytes(self, spec, config=None):
        # int8 input blocks; int32 output block + int32 VMEM accumulator
        return _gemm_tile_vmem(LaunchConfig.of(config), 1)

    def config_cost(self, spec, config):
        return _gemm_tile_steps(*self._gemm_dims(spec), config)

    def execute(self, spec, x, w, bias=None, addend=None, interpret=None,
                config=None, quant=None):
        # full override: the base cast-to-spec-dtype would truncate
        # float operands to int8 — quantization IS the cast here
        from repro.quant import symmetric
        if spec.fused_add != "none" and addend is None:
            raise ValueError(f"fused-add spec {spec.key()} needs an addend")
        f32 = jnp.float32
        x, w = x.astype(f32), w.astype(f32)
        if quant is not None and getattr(quant, "x_scale", 0) > 0:
            x_scale = jnp.asarray(quant.x_scale, f32)
        else:
            x_scale = symmetric.scale_for(symmetric.abs_max(x))
        w_scales = symmetric.channel_scales(w)          # (M,) per-channel
        xq = symmetric.quantize_to_int8(x, x_scale)
        wq = symmetric.quantize_to_int8(w, w_scales)
        acc = self._execute(spec, xq, wq, None, interpret,
                            config=LaunchConfig.of(config))
        # fp32 requantization epilogue: dequantize the int32 accumulator
        # through the outer product of scales, THEN bias/residual/
        # activation/pool at fp32 (base executors' epilogue order)
        return _xla_epilogue(
            spec, acc.astype(f32) * (x_scale * w_scales),
            None if bias is None else bias.astype(f32),
            None if addend is None else addend.astype(f32))

    def _execute(self, spec, x, w, bias, interpret, config=None):
        # bare int8 conv: int8 patch matrix (zero padding is exact under
        # symmetric quantization) -> tiled int8 GEMM -> int32 accumulator
        from repro.core.cuconv import _pad_input, _tap_views
        from repro.kernels import ops
        cfg = LaunchConfig.of(config)
        kh, kw, c, m = spec.filter_shape
        n, oh, ow, _ = spec.out_shape
        xp = _pad_input(x, *spec.padding)
        patches = jnp.stack(
            _tap_views(xp, kh, kw, oh, ow, spec.stride),
            axis=3).reshape(n * oh * ow, kh * kw * c)
        acc = ops.int8_gemm(patches, w.reshape(kh * kw * c, m),
                            interpret=interpret, tp=cfg.get("tp", 256),
                            tm=cfg.get("tm", 128), tc=cfg.get("tc", 512))
        return acc.reshape(n, oh, ow, m)


class DepthwisePallasExecutor(Executor):
    """Depthwise conv one filter tap at a time in VMEM
    (kernels/depthwise_tap.py): channels on lanes, each tap one
    broadcast multiply-add of the shifted window into a float32
    accumulator, the zero padding done in VMEM, bias and ReLU fused.
    Stride 1, odd K, ``groups == C_in == C_out``.

    Tuning space: ``nb`` (whole images per grid step), ``tc`` (channel
    tile: C, or a multiple of 128 dividing it) and ``rows`` (output rows
    one accumulator holds).  The candidates fix ``tc`` and ``rows`` as
    v5e timings of ConvNeXt-T's depthwise shapes found best (PERF.md
    §6): 128-lane channel tiles where C is a multiple of 128, and as
    many output rows as keep the float32 accumulator within 16 vector
    registers; ``nb`` is left to the cost model.
    """
    name = "depthwise_tap"
    supports_groups = True
    fuses_epilogue = True
    takes_interpret = True
    tunable = ("nb", "tc", "rows")
    epilogues = ("none", "bias", "relu", "bias_relu")

    def _supports(self, spec):
        kh, kw, cpg, m = spec.filter_shape
        if cpg != 1 or spec.groups != m:
            return False, ("depthwise_tap needs groups == C_in == C_out "
                           "(one filter per channel)")
        if not spec.unit_stride or kh % 2 == 0 or kw % 2 == 0:
            return False, "depthwise_tap needs stride 1 and an odd K"
        if not any(self.config_supports(spec, c)[0]
                   for c in self.configs(spec)):
            return False, "no depthwise block fits the VMEM budget"
        return True, "depthwise: taps accumulated in VMEM"

    def configs(self, spec):
        n, _, _, c = spec.in_shape
        _, oh, ow, _ = spec.out_shape
        tc = 128 if c % 128 == 0 else c
        row_vregs = -(-ow // 8) * -(-tc // 128)
        rows = max((r for r in range(1, oh + 1)
                    if oh % r == 0 and r * row_vregs <= 16), default=1)
        return _dedup_configs(
            ({"nb": nb, "tc": tc, "rows": rows}
             for nb in (1, 2, 4, 8, 16, 32) if n % nb == 0), {"tc": c})

    def _config_supports(self, spec, config):
        n, _, _, c = spec.in_shape
        nb, tc = config.get("nb", 1), min(config.get("tc", 128), c)
        rows = config.get("rows", 1)
        if n % nb or c % tc or spec.out_shape[1] % rows:
            return False, (f"nb={nb} must divide N={n}, tc={tc} C={c} and "
                           f"rows={rows} OH={spec.out_shape[1]}")
        return True, "config geometry ok"

    def vmem_bytes(self, spec, config=None):
        from repro.kernels.depthwise_tap import vmem_bytes
        cfg = LaunchConfig.of(config)
        return vmem_bytes(spec.in_shape, spec.filter_shape, spec.padding,
                          nb=cfg.get("nb", 1), tc=cfg.get("tc", 128),
                          itemsize=jnp.dtype(spec.dtype).itemsize,
                          bias=spec.has_bias)

    def config_cost(self, spec, config):
        # grid steps (about 0.35 us each) plus the first block's read
        # and the last block's write, which no other step overlaps
        n, h, w, c = spec.in_shape
        nb, tc = config.get("nb", 1), min(config.get("tc", 128), c)
        _, oh, ow, _ = spec.out_shape
        block = nb * (h * w + oh * ow) * tc * jnp.dtype(spec.dtype).itemsize
        return (n // nb) * (c // tc) * 0.35e-6 + block / 819e9

    def heuristic_claim(self, spec, backend):
        if backend != "tpu":
            return None                    # interpret mode elsewhere
        # outranks the library conv's grouped claim: inside a served
        # ConvNeXt-T program on a v5e, XLA's grouped conv took 17.8 ms
        # a 32-image batch at 28x28x192 against the kernel's 0.26 ms,
        # though alone it ran within 4% of it (PERF.md §6)
        return 96, "depthwise: taps accumulated in VMEM"

    def _execute(self, spec, x, w, bias, interpret, config=None):
        from repro.kernels import ops
        cfg = LaunchConfig.of(config)
        return ops.depthwise_conv(x, w, spec.padding, bias=bias,
                                  relu=spec.wants_relu,
                                  nb=cfg.get("nb", 1), tc=cfg.get("tc", 128),
                                  rows=cfg.get("rows", 1),
                                  interpret=interpret)


def _register_builtins() -> None:
    # registration order breaks negotiation ties (the first-registered
    # claim or cost wins) and orders the autotuner's candidates
    for ex in (LaxExecutor(), FusedPallasExecutor(),
               WinogradPallasExecutor(), Int8PallasExecutor(),
               DepthwisePallasExecutor()):
        register(ex)


_register_builtins()
