"""ConvSpec plan layer: one descriptor-driven entry point for all convs.

cuDNN's deployment story (and the paper's: "frameworks automatically
select the best-performing convolution algorithm for each layer") is a
descriptor + planner, not a pile of per-call-site heuristics.  This
module is that seam (DESIGN.md §4):

  ConvSpec   frozen descriptor of one convolution: shapes, stride,
             padding, dtype, epilogue, groups.  Hashable; the key for
             every cache (measured autotune, serving plans).
  plan()     the ONLY place algorithm choice lives — and it is pure
             capability negotiation over the executor registry
             (core/executors.py): a forced executor (capability-
             guarded), the persisted measured-autotune cache, the
             registered executors' heuristic region claims, then the
             cheapest supported executor by cost model.  No executor
             name is special-cased here.
  ConvPlan   executable result: call it with (x, w, bias); `explain()`
             returns a stable one-line story (executor provenance +
             dtype/accumulation) for benchmarks/debugging.

Everything downstream (core.cuconv.conv2d, models.cnn, benchmarks,
serve) routes through plan(); kernels/ops.py stays policy-free, and
capability rules live on the executors themselves (DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

# the single home of the padding type alias (cuconv re-exports it)
Pad = Union[int, Tuple[int, int], str]

EPILOGUES = ("none", "bias", "relu", "bias_relu", "gelu", "bias_gelu")

# canonical short spellings for ConvSpec.dtype / PrecisionPolicy inputs
_DTYPE_ALIASES = {"fp32": "float32", "f32": "float32",
                  "bf16": "bfloat16", "bfloat16": "bfloat16",
                  "float32": "float32", "i8": "int8", "int8": "int8"}


def canonical_dtype(dtype) -> str:
    """One canonical dtype string ('float32', 'bfloat16', ...) for any
    accepted spelling ('bf16', jnp.bfloat16, np.dtype('float32'), ...)."""
    alias = _DTYPE_ALIASES.get(str(dtype))
    if alias is not None:
        return alias
    try:
        return str(jnp.dtype(dtype).name)
    except TypeError as e:
        raise ValueError(f"unknown dtype {dtype!r}") from e


def normalize_pad(padding: Pad, kh: int, kw: int) -> Tuple[int, int]:
    """Canonical (ph, pw) for any accepted padding form.

    The single home of padding normalization (cuconv and the kernels
    import it from here).  Rejects negative amounts and wrong-length
    tuples instead of silently truncating or wrapping them.
    """
    if padding == "same":
        return (kh - 1) // 2, (kw - 1) // 2
    if padding == "valid":
        return 0, 0
    if isinstance(padding, int):
        pad = (padding, padding)
    else:
        pad = tuple(padding)
    if len(pad) != 2:
        raise ValueError(f"padding must be 'same', 'valid', an int, or a "
                         f"(ph, pw) pair; got {padding!r}")
    ph, pw = pad
    if ph < 0 or pw < 0:
        raise ValueError(f"padding must be non-negative; got {padding!r}")
    return ph, pw


def normalize_stride(stride) -> Tuple[int, int]:
    """Canonical (sh, sw) stride pair (the single home; see normalize_pad)."""
    s = (stride, stride) if isinstance(stride, int) else tuple(stride)
    if len(s) != 2:
        raise ValueError(f"stride must be an int or an (sh, sw) pair; "
                         f"got {stride!r}")
    if s[0] < 1 or s[1] < 1:
        raise ValueError(f"stride must be >= 1; got {stride!r}")
    return s


def out_size(size: int, k: int, p: int, s: int) -> int:
    """Output extent of one spatial axis: (size + 2p - k) // s + 1."""
    return (size + 2 * p - k) // s + 1


# back-compat alias (pre-graph-API name; the ONE declared home — other
# modules import it rather than re-declaring)
_norm_stride = normalize_stride


# cross-layer fusions a ConvSpec can carry in its epilogue (DESIGN.md §10)
FUSED_ADDS = ("none", "add", "add_relu")


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """Descriptor of one convolution: the planner's (and caches') key.

    ``fused_add``/``fused_pool`` describe *cross-layer* epilogue fusions
    the graph-level fusion pass (core/graph.py, DESIGN.md §10) folds
    into a conv node: a residual-add second operand (with optional
    post-add ReLU), or a trailing max/avg pool consuming the conv
    output before it ever reaches HBM.  Both ride ``key()`` so every
    cache — measured autotune, graph signatures — is fusion-distinct,
    and both are *capability-negotiated*: executors refuse fused specs
    whose fusions they do not declare (``Executor.fusions``).
    """
    in_shape: Tuple[int, int, int, int]       # (N, H, W, C) NHWC
    filter_shape: Tuple[int, int, int, int]   # (KH, KW, C/groups, M) HWIO
    stride: Tuple[int, int] = (1, 1)          # (sh, sw)
    padding: Tuple[int, int] = (0, 0)         # (ph, pw), pre-normalized
    dtype: str = "float32"
    epilogue: str = "none"                    # one of EPILOGUES
    groups: int = 1                           # feature groups (depthwise: C)
    #: residual-add fusion: a second operand (shape == out_shape) added
    #: after the bias, with 'add_relu' applying ReLU after the sum
    fused_add: str = "none"                   # none | add | add_relu
    #: pool fusion: (kind, kh, kw, sh, sw, ph, pw) applied to the conv
    #: output (post-epilogue), or () for no pool
    fused_pool: Tuple = ()

    def __post_init__(self):
        if self.epilogue not in EPILOGUES:
            raise ValueError(f"epilogue {self.epilogue!r} not in {EPILOGUES}")
        if self.fused_add not in FUSED_ADDS:
            raise ValueError(f"fused_add {self.fused_add!r} not in "
                             f"{FUSED_ADDS}")
        if self.fused_add != "none":
            if self.activation is not None:
                raise ValueError(
                    f"fused_add {self.fused_add!r} needs epilogue 'none' or "
                    f"'bias' (the activation moves AFTER the add); got "
                    f"epilogue {self.epilogue!r}")
            if self.fused_pool:
                raise ValueError("a spec carries at most one cross-layer "
                                 "fusion: fused_add and fused_pool are "
                                 "mutually exclusive")
        if self.fused_pool:
            fp = tuple(self.fused_pool)
            if len(fp) != 7 or fp[0] not in ("max", "avg"):
                raise ValueError(
                    f"fused_pool must be (kind, kh, kw, sh, sw, ph, pw) "
                    f"with kind 'max'|'avg'; got {self.fused_pool!r}")
            kind, pkh, pkw, psh, psw, pph, ppw = fp
            if min(pkh, pkw, psh, psw) < 1 or min(pph, ppw) < 0:
                raise ValueError(f"fused_pool geometry must be positive "
                                 f"windows/strides and non-negative "
                                 f"padding; got {self.fused_pool!r}")
            object.__setattr__(self, "fused_pool",
                               (str(kind),) + tuple(map(int, fp[1:])))
        if not isinstance(self.groups, int) or self.groups < 1:
            raise ValueError(f"groups must be a positive int; "
                             f"got {self.groups!r}")
        if self.in_shape[3] != self.filter_shape[2] * self.groups:
            raise ValueError(
                f"channel mismatch: input {self.in_shape} needs filter "
                f"depth {self.in_shape[3]} / groups={self.groups}; "
                f"filter {self.filter_shape}")
        if self.filter_shape[3] % self.groups:
            raise ValueError(f"output channels {self.filter_shape[3]} not "
                             f"divisible by groups={self.groups}")
        # direct construction must be as strict as the normalize_* path
        if len(self.stride) != 2 or any(s < 1 for s in self.stride):
            raise ValueError(f"stride must be an (sh, sw) pair >= 1; "
                             f"got {self.stride!r}")
        if len(self.padding) != 2 or any(p < 0 for p in self.padding):
            raise ValueError(f"padding must be a non-negative (ph, pw) "
                             f"pair; got {self.padding!r}")
        # canonicalize dtype so 'bf16' and 'bfloat16' share cache keys
        object.__setattr__(self, "dtype", canonical_dtype(self.dtype))
        if any(d <= 0 for d in self.out_shape):
            raise ValueError(f"spec produces non-positive output shape "
                             f"{self.out_shape}: input {self.in_shape}, "
                             f"filter {self.filter_shape}, stride "
                             f"{self.stride}, padding {self.padding}")

    @classmethod
    def for_conv(cls, x, w, stride=1, padding: Pad = "same",
                 bias=None, activation: Optional[str] = None,
                 groups: int = 1) -> "ConvSpec":
        """Build a spec from (possibly traced) operands + call options.

        Unknown activations are an error, not a silent epilogue "none":
        the planner only knows how to fuse what EPILOGUES names.
        """
        if activation not in (None, "none", "relu", "gelu"):
            raise ValueError(
                f"activation {activation!r} not supported; the planner "
                f"fuses None, 'relu' or 'gelu' (epilogues: {EPILOGUES})")
        act = None if activation in (None, "none") else activation
        kh, kw = int(w.shape[0]), int(w.shape[1])
        epi = "_".join(p for p in ("bias" if bias is not None else None,
                                   act) if p) or "none"
        return cls(tuple(map(int, x.shape)), tuple(map(int, w.shape)),
                   normalize_stride(stride), normalize_pad(padding, kh, kw),
                   str(x.dtype), epi, int(groups))

    # -- derived geometry ------------------------------------------------
    @property
    def out_shape(self) -> Tuple[int, int, int, int]:
        n, h, w, _ = self.in_shape
        kh, kw, _, m = self.filter_shape
        (sh, sw), (ph, pw) = self.stride, self.padding
        return (n, out_size(h, kh, ph, sh), out_size(w, kw, pw, sw), m)

    @property
    def is_1x1(self) -> bool:
        return self.filter_shape[0] == 1 and self.filter_shape[1] == 1

    @property
    def unit_stride(self) -> bool:
        return self.stride == (1, 1)

    @property
    def has_bias(self) -> bool:
        return self.epilogue.startswith("bias")

    @property
    def activation(self) -> Optional[str]:
        """The epilogue's activation: ``"relu"``, ``"gelu"`` (the exact
        erf form) or None."""
        act = self.epilogue.rpartition("_")[2]
        return act if act in ("relu", "gelu") else None

    @property
    def wants_relu(self) -> bool:
        return self.activation == "relu"

    @property
    def has_fusion(self) -> bool:
        """Does this spec carry a cross-layer fusion (add or pool)?"""
        return self.fused_add != "none" or bool(self.fused_pool)

    @property
    def final_shape(self) -> Tuple[int, int, int, int]:
        """Shape this spec's execution ultimately yields: ``out_shape``
        for plain/fused-add specs, the pooled shape for fused-pool."""
        if not self.fused_pool:
            return self.out_shape
        _, pkh, pkw, psh, psw, pph, ppw = self.fused_pool
        n, oh, ow, m = self.out_shape
        return (n, out_size(oh, pkh, pph, psh),
                out_size(ow, pkw, ppw, psw), m)

    def unfused(self) -> "ConvSpec":
        """This spec with cross-layer fusions stripped (the plain conv
        the fusion pass started from; epilogue/bias are preserved)."""
        if not self.has_fusion:
            return self
        return dataclasses.replace(self, fused_add="none", fused_pool=())

    def key(self) -> str:
        """Stable string key for persisted caches.

        The dtype segment makes keys precision-distinct (a bf16 plan can
        never serve an fp32 spec); ungrouped specs keep the historical
        key shape (no ``-g`` segment) so pre-groups persisted autotune
        entries stay valid.
        """
        n, h, w, c = self.in_shape
        kh, kw, _, m = self.filter_shape
        g = f"-g{self.groups}" if self.groups != 1 else ""
        fused = ""
        if self.fused_add != "none":
            fused = "-fadd" if self.fused_add == "add" else "-faddrelu"
        elif self.fused_pool:
            kind, pkh, pkw, psh, psw, pph, ppw = self.fused_pool
            fused = (f"-fpool{kind}{pkh}x{pkw}s{psh}x{psw}p{pph}x{ppw}")
        return (f"n{n}h{h}w{w}c{c}-k{kh}x{kw}m{m}-s{self.stride[0]}x"
                f"{self.stride[1]}-p{self.padding[0]}x{self.padding[1]}-"
                f"{self.dtype}-{self.epilogue}{g}{fused}")


# ---------------------------------------------------------------------------
# capability: a thin delegation to the executor registry (the rules
# themselves live on the registered executors — DESIGN.md §8)

def supports(algorithm: str, spec: ConvSpec) -> Tuple[bool, str]:
    """Can `algorithm` execute `spec` exactly (ignoring speed)?

    Back-compat wrapper over ``executors.get(algorithm).supports(spec)``.
    """
    from repro.core import executors
    return executors.get(algorithm).supports(spec)


def heuristic_algorithm(spec: ConvSpec, backend: str) -> Tuple[str, str]:
    """The negotiated choice absent force/measurement: the executors'
    paper-region claims (figs 5-7), else the cheapest supported
    executor by cost model.  Back-compat wrapper over
    ``executors.negotiate``."""
    from repro.core import executors
    name, _source, reason = executors.negotiate(spec, backend)
    return name, reason


# ---------------------------------------------------------------------------
# plan

# Observable resolution count: every plan() call increments it, and
# NOTHING else does.  The graph layer's plan-once contract is asserted
# against this ("warmup then N inferences adds zero resolutions").
PLAN_STATS = {"resolutions": 0}


def reset_plan_stats() -> int:
    """Zero the resolution counter (tests use this, not dict-poking);
    returns the count that was discarded."""
    old = PLAN_STATS["resolutions"]
    PLAN_STATS["resolutions"] = 0
    return old


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """Executable ``(algorithm, launch config)`` choice for one ConvSpec."""
    spec: ConvSpec
    algorithm: str
    source: str          # heuristic | cost | measured | forced | fallback
    reason: str
    backend: str = "cpu"
    interpret: Optional[bool] = None  # forwarded to Pallas executors
    #: resolved launch config (executors.LaunchConfig; empty/None for
    #: untunable executors) and its provenance
    config: Optional[object] = None
    config_source: str = "default"    # default | measured | forced
    #: quantization payload (quant.policy.QuantInfo) for int8 specs: the
    #: calibrated per-tensor activation scale + its provenance.  None on
    #: fp plans AND on int8 plans resolved outside the quantize pass
    #: (autotune timing) — the executor then falls back to a dynamic
    #: in-trace scale
    quant: Optional[object] = None

    @property
    def executor(self):
        """The registry entry this plan resolves to."""
        from repro.core import executors
        return executors.get(self.algorithm)

    def explain(self) -> str:
        ex = self.executor
        cfg = (f" cfg[{self.config_source}]={self.config.key()}"
               if self.config else "")
        q = f" quant[{self.quant.key()}]" if self.quant else ""
        return (f"{self.spec.key()} -> {self.algorithm} "
                f"[{self.source}]{cfg}{q} dtype={self.spec.dtype} "
                f"accum={ex.accum} {self.reason}")

    # -- execution -------------------------------------------------------
    def __call__(self, x, w, bias=None, addend=None):
        spec = self.spec
        if spec.has_bias and bias is None:
            raise ValueError(f"plan epilogue {spec.epilogue!r} needs a bias")
        if spec.fused_add != "none" and addend is None:
            raise ValueError(f"plan for fused-add spec {spec.key()} needs "
                             f"an addend (the residual operand)")
        if spec.fused_add == "none" and addend is not None:
            raise ValueError(f"plan for spec {spec.key()} does not take an "
                             f"addend (fused_add='none')")
        kwargs = {}
        if self.quant is not None:
            # only int8-aware executors ever receive the payload — the
            # quantize pass attaches it exclusively to plans whose
            # executor declared int8 support
            kwargs["quant"] = self.quant
        return self.executor.execute(
            spec, x, w, bias=bias if spec.has_bias else None,
            addend=addend, interpret=self.interpret, config=self.config,
            **kwargs)


def resolve_config(spec: ConvSpec, algorithm: str,
                   backend: str) -> Tuple[object, str]:
    """``(launch config, provenance)`` for an already-chosen algorithm.

    The persisted measured winner serves if it is still valid for this
    spec under the executor's current declarations (a stale config —
    e.g. ``rows`` larger than OH after a geometry change, or a
    tightened VMEM budget — is dropped, never served); otherwise the
    executor's model-chosen ``default_config``.
    """
    from repro.core import autotune, executors
    ex = executors.get(algorithm)
    cached = autotune.cached_config(spec, backend, algorithm)
    if cached is not None and ex.config_supports(spec, cached)[0]:
        return cached, "measured"
    return ex.default_config(spec), "default"


def _with_config(spec, algorithm, source, reason, backend, interpret,
                 config) -> ConvPlan:
    """Attach the resolved (or caller-forced) launch config to a plan."""
    from repro.core import executors
    if config is not None:
        cfg = executors.LaunchConfig.of(config)
        ok, why = executors.get(algorithm).config_supports(spec, cfg)
        if not ok:
            raise ValueError(
                f"forced launch config {cfg.as_dict()} is not supported "
                f"by executor {algorithm!r} for spec {spec.key()}: {why}")
        return ConvPlan(spec, algorithm, source, reason, backend, interpret,
                        cfg, "forced")
    cfg, cfg_src = resolve_config(spec, algorithm, backend)
    return ConvPlan(spec, algorithm, source, reason, backend, interpret,
                    cfg, cfg_src)


def plan(spec: ConvSpec, force: Optional[str] = None,
         backend: Optional[str] = None,
         interpret: Optional[bool] = None,
         tune: Optional[str] = None,
         config=None) -> ConvPlan:
    """All conv algorithm choice, in one place — capability negotiation
    over the executor registry — resolving an ``(algorithm, launch
    config)`` pair.

    Algorithm order: forced executor (capability-guarded; an unsupported
    forced choice takes the executor's declared fallback, except grouped
    specs, which raise rather than silently running a different
    algorithm than the caller demanded) > persisted measured-autotune
    winner > the executors' heuristic region claims > cheapest supported
    executor.

    ``tune`` runs the measured sweep first (``"algo"``: time every
    capable executor — with ``force`` the sweep still runs and records
    the unforced winner, the pin only decides what THIS plan serves;
    ``"full"``: sweep the candidate launch configs of the forced
    executor, or of the winner after an algorithm sweep) and persists
    the winners, so the very plan returned already serves them — and
    every later ``plan()`` replays them from cache with zero
    re-measurement.  ``config`` forces a launch config
    (validated against the executor's ``config_supports`` — an
    infeasible forced config raises naming executor, config and spec);
    otherwise the persisted measured config (if still valid) or the
    executor's model-chosen ``default_config`` rides the plan.
    """
    PLAN_STATS["resolutions"] += 1
    backend = backend or jax.default_backend()
    from repro.core import executors

    if tune not in (None, "algo", "full"):
        raise ValueError(f'tune must be None, "algo" or "full"; '
                         f'got {tune!r}')
    if tune is not None:
        from repro.core import autotune
        autotune.tune_spec(spec, tune=tune, backend=backend,
                           algorithm=force)

    if force is not None:
        ex = executors.get(force)      # KeyError names the registry
        ok, why = ex.supports(spec)
        if ok:
            return _with_config(spec, force, "forced", why, backend,
                                interpret, config)
        if spec.groups != 1 and not ex.supports_groups:
            # a grouped spec has no numerically-equivalent stand-in among
            # ungrouped executors: falling back would silently ignore the
            # caller's explicit choice, so refuse loudly instead
            raise ValueError(
                f"forced algorithm {force!r} cannot execute grouped spec "
                f"{spec.key()} (groups={spec.groups}): {why}.  Force an "
                f"executor that declares grouped support (e.g. 'lax') or "
                f"let plan() negotiate.")
        fb, fb_why = ex.fallback(spec)
        fb_ok, fb_refusal = executors.get(fb).supports(spec)
        if not fb_ok:
            raise ValueError(
                f"forced algorithm {force!r} cannot execute {spec.key()} "
                f"({why}), and its declared fallback {fb!r} cannot either "
                f"({fb_refusal})")
        return _with_config(spec, fb, "fallback",
                            f"{force} unsupported ({why}); {fb_why}",
                            backend, interpret, config)

    from repro.core import autotune
    measured = autotune.cached_best(spec, backend)
    if measured is not None and executors.capable(measured, spec):
        return _with_config(spec, measured, "measured",
                            "persisted autotune winner", backend,
                            interpret, config)

    algo, source, reason = executors.negotiate(spec, backend)
    return _with_config(spec, algo, source, reason, backend, interpret,
                        config)
