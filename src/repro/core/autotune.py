"""Measured per-layer algorithm + launch-config selection, persisted
across processes.

Mirrors the deployment behaviour the paper relies on ("most frameworks
automatically select the best-performing convolution algorithm for each
convolutional layer") — and the paper's own per-configuration *launch
selection* (thread-block geometry per convolution configuration, the
lever maxDNN showed is worth large factors on its own):

  * heuristic mode — the registered executors' region claims
    (``executors.negotiate``, the paper's measured regions);
  * measured mode — ``measure_algorithm`` times every viable candidate
    executor (compiled, synced); ``measure_config`` then sweeps the
    winner's candidate *launch configs* (tile sizes, rows-per-step —
    ``Executor.configs``, VMEM-pruned via ``config_supports`` before
    anything is timed).  ``tune_spec`` is the one entry point
    ``plan(tune=...)`` and ``GraphPlan.warmup(tune=...)`` share.

Winners are persisted keyed by ``(backend, ConvSpec.key())`` in a JSON
cache under ``$REPRO_CACHE_DIR`` (default ``~/.cache/repro``) as
schema-versioned entries::

    {"schema": 2, "algorithm": "cuconv_pallas",   # measured winner (or null)
     "configs": {"cuconv_pallas": {"tm": 256, "rows": 4}}}

so one process's measurement sweep pays for every later process.
``configs`` maps *per algorithm*: tuning a pinned/forced executor's
launch configs records under that executor's key without overwriting
the genuinely measured ``algorithm`` winner (and a config is only ever
served back for the executor it was measured with).  Unversioned
entries (the pre-config era persisted bare algorithm strings) and
foreign-schema entries are dropped on read — never misdecoded into the
``(algorithm, config)`` shape — and re-measured.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.convspec import ConvPlan, ConvSpec
from repro.core.plancache import JsonCache

#: persisted-entry schema.  v1 was the bare algorithm string (implicitly
#: unversioned); v2 is {"schema": 2, "algorithm": str[, "config": {...}]}.
AUTOTUNE_SCHEMA = 2

_STORE = JsonCache("autotune.json")

#: observable measurement effort — tests assert the replay-from-cache
#: path performs ZERO re-measurement against these counters.
#: ``failures`` lists every candidate a sweep dropped because it raised
#: (a kernel the backend's compiler refused, say): one
#: ``{"spec", "executor", "config", "error"}`` dict each, so a sweep
#: never loses a candidate silently
MEASURE_STATS = {"algo_sweeps": 0, "config_sweeps": 0, "fusion_sweeps": 0,
                 "timed_calls": 0, "failures": []}


def reset_measure_stats() -> dict:
    """Zero the measurement counters and clear ``failures``; returns the
    discarded values."""
    old = {k: (list(v) if isinstance(v, list) else v)
           for k, v in MEASURE_STATS.items()}
    for k, v in MEASURE_STATS.items():
        MEASURE_STATS[k] = [] if isinstance(v, list) else 0
    return old


def _record_failure(spec: ConvSpec, executor: str, config,
                    err: Exception) -> None:
    MEASURE_STATS["failures"].append({
        "spec": spec.key(), "executor": executor,
        "config": config.as_dict() if config else {},
        "error": f"{type(err).__name__}: {err}".splitlines()[0][:500]})


def _key(spec: ConvSpec, backend: str) -> str:
    # the epilogue rides whatever algorithm wins — measurements taken
    # without it must serve the bias/ReLU-fused specs conv_block builds,
    # so the cache key is epilogue-insensitive
    if spec.epilogue != "none":
        spec = dataclasses.replace(spec, epilogue="none")
    return f"{backend}/{spec.key()}"


def _entry(spec: ConvSpec, backend: Optional[str]) -> Optional[dict]:
    """The persisted entry for this spec, schema-gated: unversioned
    (pre-config bare strings) or foreign-schema values are dropped."""
    e = _STORE.get(_key(spec, backend or jax.default_backend()))
    if not isinstance(e, dict) or e.get("schema") != AUTOTUNE_SCHEMA:
        return None
    algo = e.get("algorithm")
    if algo is not None and not isinstance(algo, str):
        return None         # algorithm may be null: config-only entries
    return e


def cached_best(spec: ConvSpec, backend: Optional[str] = None
                ) -> Optional[str]:
    """Persisted measured winner for this spec on this backend, if any."""
    e = _entry(spec, backend)
    return None if e is None else e.get("algorithm")


def cached_config(spec: ConvSpec, backend: Optional[str] = None,
                  algorithm: Optional[str] = None):
    """Persisted measured launch config (``executors.LaunchConfig``) for
    ``algorithm`` on this spec (default: the entry's measured winner),
    or None.

    Configs are stored per algorithm — one tuned for an executor is
    only ever served back for that executor.  Validity against the
    executor's *current* declarations is the caller's job
    (``convspec.resolve_config`` gates through ``config_supports``).
    """
    from repro.core.executors import LaunchConfig
    e = _entry(spec, backend)
    if e is None:
        return None
    if algorithm is None:
        algorithm = e.get("algorithm")
        if algorithm is None:
            return None
    cfgs = e.get("configs")
    cfg = cfgs.get(algorithm) if isinstance(cfgs, dict) else None
    if not isinstance(cfg, dict):
        return None
    try:
        return LaunchConfig.of(cfg)
    except ValueError:
        return None                 # malformed dims: drop, re-measure


def _merged_entry(spec: ConvSpec, backend: str) -> dict:
    e = _entry(spec, backend)
    if e is None:
        e = {"schema": AUTOTUNE_SCHEMA, "algorithm": None, "configs": {}}
    if not isinstance(e.get("configs"), dict):
        e["configs"] = {}
    return e


def record_best(spec: ConvSpec, backend: str, algorithm: str,
                config=None) -> None:
    """Persist a measured winner (schema-versioned).  ``config``, if
    given, records under the winner's per-algorithm config slot."""
    entry = _merged_entry(spec, backend)
    entry["algorithm"] = algorithm
    if config:
        from repro.core.executors import LaunchConfig
        entry["configs"][algorithm] = LaunchConfig.of(config).as_dict()
    _STORE.put(_key(spec, backend), entry)


def record_config(spec: ConvSpec, backend: str, algorithm: str,
                  config) -> None:
    """Persist a measured launch config for ``algorithm`` WITHOUT
    touching the entry's measured-winner field — tuning a pinned/forced
    executor must not make later unforced plans serve it as the
    'measured' algorithm it never was."""
    from repro.core.executors import LaunchConfig
    entry = _merged_entry(spec, backend)
    entry["configs"][algorithm] = LaunchConfig.of(config).as_dict()
    _STORE.put(_key(spec, backend), entry)


def clear_cache() -> None:
    """Drop the in-memory mirror (tests); the JSON file is untouched."""
    _STORE.clear()


# ---------------------------------------------------------------------------
# public API

def default_candidates(spec: ConvSpec) -> Sequence[str]:
    """Every registered executor that can execute ``spec`` exactly —
    including the Pallas kernels this repo exists to showcase."""
    from repro.core import executors
    return executors.supporting(spec)


def _time_plan(p, x, w, bias, repeats: int, addend=None) -> float:
    """Median wall time of a jitted plan execution (compiled, synced)."""
    fn = jax.jit(p)
    args = (x, w, bias) if addend is None else (x, w, bias, addend)
    fn(*args).block_until_ready()    # compile + warm
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        ts.append(time.perf_counter() - t0)
    MEASURE_STATS["timed_calls"] += 1 + repeats
    return float(np.median(ts))


def _fused_operands(spec: ConvSpec):
    """Synthesized (x, w, bias, addend) for timing a bare spec."""
    dtype = jnp.dtype(spec.dtype)
    x = jnp.zeros(spec.in_shape, dtype)
    w = jnp.zeros(spec.filter_shape, dtype)
    b = jnp.zeros((spec.filter_shape[3],), dtype) if spec.has_bias else None
    a = (jnp.zeros(spec.out_shape, dtype)
         if spec.fused_add != "none" else None)
    return x, w, b, a


def measure_algorithm(x, w, stride=1, padding="same", repeats=3,
                      candidates: Optional[Sequence[str]] = None,
                      bias=None, activation: Optional[str] = None,
                      groups: int = 1,
                      spec: Optional[ConvSpec] = None) -> str:
    """Time every viable candidate (compiled, synced), persist the winner.

    The cuDNN-style exhaustive search the paper used for its baselines;
    ``plan()`` serves the recorded winner to every later process.

    ``candidates=None`` means every registered executor filtered by its
    declared capabilities (dtype included) — so the measured mode can
    pick the Pallas kernels, not just the XLA family, and a bf16 spec
    only times executors that declare bf16.  ``bias``/``activation``
    ride into the timed executions, so fused-epilogue paths are measured
    exactly as they deploy (epilogue in-kernel on the fused Pallas path,
    XLA ops elsewhere); the persisted key stays epilogue-insensitive
    (but dtype-distinct: ConvSpec.key() carries the dtype).  Each
    executor is timed under its model-chosen ``default_config`` (the
    per-config sweep is ``measure_config``).  ``spec`` overrides the
    operand-derived descriptor — the only way a *fused* spec (cross-
    layer add/pool fields; they cannot be inferred from operands) is
    swept as itself.
    """
    from repro.core import executors
    if spec is None:
        spec = ConvSpec.for_conv(x, w, stride, padding, bias=bias,
                                 activation=activation, groups=groups)
    addend = (jnp.zeros(spec.out_shape, jnp.dtype(spec.dtype))
              if spec.fused_add != "none" else None)
    backend = jax.default_backend()
    hit = cached_best(spec, backend)
    # a persisted winner only short-circuits the sweep while it is still
    # a registered, capable executor — a stale entry (unregistered
    # plugin, tightened VMEM budget) re-measures and gets overwritten
    if hit is not None and executors.capable(hit, spec):
        return hit
    if candidates is None:
        candidates = default_candidates(spec)
    MEASURE_STATS["algo_sweeps"] += 1
    best, best_t = None, float("inf")
    for name in candidates:
        # unknown or incapable candidates are skipped, not fatal: an
        # explicit candidate list may name a plugin this process never
        # registered, and the sweep should still time the rest
        if not executors.capable(name, spec):
            continue
        # time through a ConvPlan so the epilogue runs as deployed;
        # default_config rides inside the guard so one candidate's
        # broken tuning declarations degrade the sweep, not crash it
        cfg = None
        try:
            cfg = executors.get(name).default_config(spec)
            p = ConvPlan(spec, name, "candidate", "autotune timing",
                         backend, config=cfg)
            t = _time_plan(p, x, w, bias, repeats, addend)
        except Exception as e:
            _record_failure(spec, name, cfg, e)
            continue
        if t < best_t:
            best, best_t = name, t
    if best is None:
        # nothing timed successfully: don't persist a fake "measured"
        # winner — leave the planner on its heuristic/cost tiers and
        # report what negotiation would run
        return executors.negotiate(spec, backend)[0]
    record_best(spec, backend, best)
    return best


def measure_config(x, w, stride=1, padding="same", repeats=3,
                   algorithm: Optional[str] = None,
                   candidates=None, bias=None,
                   activation: Optional[str] = None,
                   groups: int = 1,
                   spec: Optional[ConvSpec] = None) -> Tuple[str, object]:
    """Sweep an executor's candidate launch configs, persist the winner.

    ``algorithm=None`` tunes the spec's measured winner (else the
    negotiated choice).  Candidates default to the executor's declared
    ``configs(spec)``, pruned through ``config_supports`` (VMEM budget,
    geometry rules) BEFORE anything is timed.  The winning
    ``(algorithm, config)`` pair is persisted under the versioned
    schema; with default candidates a persisted, still-valid config
    short-circuits the sweep — replaying a tuned spec costs zero
    measurements.  An *explicit* ``candidates`` list is a request to
    measure exactly those configs: it is always timed (and its winner
    overwrites the persisted config).  Returns
    ``(algorithm, LaunchConfig)``.  ``spec`` overrides the operand-
    derived descriptor (fused cross-layer specs; see
    ``measure_algorithm``).
    """
    from repro.core import executors
    if spec is None:
        spec = ConvSpec.for_conv(x, w, stride, padding, bias=bias,
                                 activation=activation, groups=groups)
    addend = (jnp.zeros(spec.out_shape, jnp.dtype(spec.dtype))
              if spec.fused_add != "none" else None)
    backend = jax.default_backend()
    if algorithm is None:
        algorithm = cached_best(spec, backend)
        if algorithm is None or not executors.capable(algorithm, spec):
            algorithm = executors.negotiate(spec, backend)[0]
    ex = executors.get(algorithm)
    if not ex.supports(spec)[0]:
        # an explicitly named executor that cannot run the spec at all:
        # nothing to sweep (and nothing to persist — a timed config for
        # an incapable executor would be meaningless)
        return algorithm, ex.default_config(spec)
    if candidates is None:
        # default sweep: a persisted, still-valid config replays free
        hit = cached_config(spec, backend, algorithm)
        if hit is not None and ex.config_supports(spec, hit)[0]:
            return algorithm, hit
        candidates = ex.configs(spec)
    feasible = []
    for c in candidates:
        c = executors.LaunchConfig.of(c)
        if ex.config_supports(spec, c)[0] and c not in feasible:
            feasible.append(c)
    if not feasible or (len(feasible) == 1 and not feasible[0]):
        # untunable executor (or nothing survived pruning): nothing to
        # sweep, nothing to persist beyond the algorithm itself
        return algorithm, ex.default_config(spec)
    MEASURE_STATS["config_sweeps"] += 1
    best, best_t = None, float("inf")
    for cfg in feasible:
        p = ConvPlan(spec, algorithm, "candidate",
                     "autotune config timing", backend, config=cfg,
                     config_source="candidate")
        try:
            t = _time_plan(p, x, w, bias, repeats, addend)
        except Exception as e:
            _record_failure(spec, algorithm, cfg, e)
            continue
        if t < best_t:
            best, best_t = cfg, t
    if best is None:
        return algorithm, ex.default_config(spec)
    record_config(spec, backend, algorithm, best)
    return algorithm, best


def fusion_verdict(spec: ConvSpec, backend: Optional[str] = None
                   ) -> Optional[bool]:
    """Persisted fused-vs-unfused arbitration for a fused spec.

    True: the fused kernel measured at least as fast as its unfused
    decomposition; False: fusion measured slower (the graph pass keeps
    the nodes separate); None: never measured (the pass fuses on the
    cost model's word — fusion strictly removes HBM round trips).
    """
    e = _entry(spec, backend)
    if e is None or not isinstance(e.get("fusion"), dict):
        return None
    return bool(e["fusion"].get("wins", True))


def measure_fusion(spec: ConvSpec, backend: Optional[str] = None,
                   repeats: int = 3, force: bool = False
                   ) -> Optional[bool]:
    """Time a fused spec against its unfused decomposition and persist
    the verdict (``tune="full"`` arbitration, DESIGN.md §10).

    The unfused side runs the SAME conv plan the pre-fusion graph would
    have resolved, followed by the XLA add/ReLU or pool the consumed
    node would have executed — an apples-to-apples per-layer race.  The
    verdict persists under the fused spec's (fusion-distinct) cache key
    as ``{"fusion": {"wins": bool, "fused_us": ..., "unfused_us": ...}}``
    and replays free; ``force=True`` re-measures.  Returns the verdict,
    or None when timing failed (nothing is persisted then).
    """
    from repro.core import convspec
    from repro.kernels import ops
    if not spec.has_fusion:
        raise ValueError(f"spec {spec.key()} carries no fusion to measure")
    backend = backend or jax.default_backend()
    if not force:
        hit = fusion_verdict(spec, backend)
        if hit is not None:
            return hit
    MEASURE_STATS["fusion_sweeps"] += 1
    x, w, b, addend = _fused_operands(spec)
    fused_plan = convspec.plan(spec, backend=backend)
    base_plan = convspec.plan(spec.unfused(), backend=backend)
    if spec.fused_add != "none":
        post_relu = spec.fused_add == "add_relu"

        def unfused(x, w, bias=None, addend=None):
            y = base_plan(x, w, bias) + addend
            return jnp.maximum(y, 0) if post_relu else y
    else:
        kind, pkh, pkw, psh, psw, pph, ppw = spec.fused_pool

        def unfused(x, w, bias=None):
            return ops.pool2d(base_plan(x, w, bias), kind=kind,
                              window=(pkh, pkw), stride=(psh, psw),
                              padding=(pph, ppw))
    try:
        fused_t = _time_plan(fused_plan, x, w, b, repeats, addend)
        unfused_t = _time_plan(unfused, x, w, b, repeats, addend)
    except Exception as e:
        _record_failure(spec, fused_plan.algorithm, fused_plan.config, e)
        return None              # nothing timed: leave the verdict open
    wins = fused_t <= unfused_t
    entry = _merged_entry(spec, backend)
    entry["fusion"] = {"wins": wins,
                       "fused_us": round(fused_t * 1e6, 3),
                       "unfused_us": round(unfused_t * 1e6, 3)}
    _STORE.put(_key(spec, backend), entry)
    return wins


def tune_spec(spec: ConvSpec, *, tune: str = "algo",
              backend: Optional[str] = None, repeats: int = 3,
              algorithm: Optional[str] = None) -> Tuple[str, object]:
    """Measure a bare ConvSpec (operands synthesized from its shapes):
    the one tuning entry point ``plan(tune=...)``,
    ``GraphPlan.warmup(tune=...)`` and the serve engine share.

    ``tune="algo"`` runs the executor sweep — even when ``algorithm``
    pins the executor, so the sweep's winner is recorded for later
    *unforced* plans (the pin only decides what this plan serves).
    ``tune="full"`` then sweeps the candidate launch configs of the
    pinned executor (if any) or of the sweep's winner.  Returns
    ``(algorithm, LaunchConfig | None)``.
    """
    if tune not in ("algo", "full"):
        raise ValueError(f'tune must be "algo" or "full"; got {tune!r}')
    backend = backend or jax.default_backend()
    if backend != jax.default_backend():
        # timing on this process's backend and recording it under
        # another backend's key would silently discard the sweep
        raise ValueError(
            f"measured tuning must run on the target backend: asked for "
            f"{backend!r} but this process runs {jax.default_backend()!r}")
    x, w, b, _ = _fused_operands(spec)
    act = spec.activation
    kwargs = dict(stride=spec.stride, padding=spec.padding, repeats=repeats,
                  bias=b, activation=act, groups=spec.groups, spec=spec)
    if tune == "algo" or algorithm is None:
        best = measure_algorithm(x, w, **kwargs)
        if algorithm is None:
            algorithm = best
    if tune == "full":
        if spec.has_fusion:
            # fused-vs-unfused arbitration: the graph pass consults the
            # persisted verdict on its next rewrite of this spec
            measure_fusion(spec, backend=backend, repeats=repeats)
        return measure_config(x, w, algorithm=algorithm, **kwargs)
    return algorithm, None
