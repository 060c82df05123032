#!/usr/bin/env python3
"""Chip smoke run: the planned cuConv path and image serving, once, on TPU.

Run from the checkout root, one process (it holds the chip):

  python3 chip_smoke.py              phases A and B on one chip
  python3 chip_smoke.py --chips 4    phase C only, on four chips
  python3 chip_smoke.py --cpu-rehearsal [--chips 4]
                                     the same phases at reduced size on
                                     the CPU (TPU planning, Pallas in
                                     interpret mode); never reports ok

Phase A plans every paper layer (configs/cnn_paper: PROFILED, RESNET50
and VGG19 at batch 8, fp32, bias+ReLU) with ``plan()``, runs it and
compares it with ``lax.conv_general_dilated(precision=HIGHEST)``.
Phase B serves ``resnet_like`` at 224x224 through ``AsyncServeFrontend``
and compares every response with the same model forced to ``lax``.
Phase C serves it through ``ShardedServeDispatcher`` on a 4-device mesh
and requires outputs bitwise equal to the single-device engine.

On success the last stdout line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check exits non-zero; without a TPU it exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: max |out - ref| / max |ref| allowed everywhere.  XLA's TPU default
#: precision feeds f32 matmuls to the MXU as bf16 (unit roundoff 2^-9);
#: summed over a contraction of random-sign products, and through F(4,3)
#: Winograd transforms, that stays well inside 2e-2 — while a wrong tap,
#: offset or epilogue gives an O(1) error.
TOL = 2e-2
SEED = 0


class SmokeFailure(Exception):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _rel_err(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def _is_pallas(algorithm: str) -> bool:
    from repro.core import executors
    return executors.get(algorithm).takes_interpret


def _paper_specs(reduced: bool):
    """(label, ConvSpec) for every Phase A layer."""
    from repro.configs import cnn_paper
    from repro.core.convspec import ConvSpec
    rows = [(label, n, hw, k, m, c)
            for label, (hw, n, k, m, c) in cnn_paper.PROFILED.items()]
    for net in ("resnet50", "vgg19"):
        rows += [(f"{net}-{hw}-{k}-{m}-{c}", 8, hw, k, m, c)
                 for hw, k, m, c in cnn_paper.NETWORKS[net]]
    specs = []
    for label, n, hw, k, m, c in rows:
        if reduced:
            n, hw, m, c = min(n, 2), min(hw, 9), min(m, 16), min(c, 16)
        pad = (k - 1) // 2
        specs.append((label, ConvSpec((n, hw, hw, c), (k, k, c, m),
                                      padding=(pad, pad),
                                      epilogue="bias_relu")))
    return specs


def phase_a(backend, reduced: bool) -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.core import convspec

    print("[A] paper layers: plan() -> run -> compare with lax HIGHEST")
    key = jax.random.PRNGKey(SEED)
    worst = 0.0
    for label, spec in _paper_specs(reduced):
        kx, kw, kb, key = jax.random.split(key, 4)
        x = jax.random.normal(kx, spec.in_shape, jnp.float32)
        w = jax.random.normal(kw, spec.filter_shape, jnp.float32)
        w = w / (spec.filter_shape[0] * spec.filter_shape[1]
                 * spec.filter_shape[2]) ** 0.5
        b = jax.random.normal(kb, (spec.filter_shape[3],), jnp.float32)
        p = convspec.plan(spec, backend=backend)
        _check(p.source != "fallback",
               f"{label}: resolved through fallback: {p.explain()}")
        compiled = jax.jit(p).lower(x, w, b).compile()
        if backend is None and _is_pallas(p.algorithm):
            _check("tpu_custom_call" in compiled.as_text(),
                   f"{label}: {p.algorithm} compiled without "
                   f"tpu_custom_call (interpret mode?)")
        y = compiled(x, w, b)
        ph, pw = spec.padding
        ref = jax.nn.relu(lax.conv_general_dilated(
            x, w, spec.stride, ((ph, ph), (pw, pw)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST) + b)
        err = _rel_err(y, ref)
        worst = max(worst, err)
        cfg = p.config.key() if p.config else "-"
        print(f"[A] {label:22s} {spec.key():52s} -> {p.algorithm:24s} "
              f"[{p.source}] cfg={cfg} rel_err={err:.3e}", flush=True)
        _check(err <= TOL, f"{label}: rel err {err:.3e} > {TOL}")
    print(f"[A] pass: worst rel err {worst:.3e} <= {TOL}")


def _requests(shape, n_req: int, max_images: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    return [rng.standard_normal((int(rng.integers(1, max_images + 1)),)
                                + shape).astype(np.float32)
            for _ in range(n_req)]


def _lax_reference(model, params, images, bucket: int):
    """The model forced to ``lax`` at HIGHEST matmul precision, on the
    concatenated images in zero-padded chunks of ``bucket`` (one
    compile)."""
    import jax
    import numpy as np
    x = np.concatenate(images)
    pad = (-len(x)) % bucket
    x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
    fn = jax.jit(lambda p, xb: model.apply(p, xb, algorithm="lax"))
    with jax.default_matmul_precision("highest"):
        outs = [np.asarray(fn(params, x[i:i + bucket]))
                for i in range(0, len(x), bucket)]
    return np.concatenate(outs)[:len(x) - pad]


def phase_b(backend, reduced: bool) -> None:
    import jax
    import numpy as np

    from repro.core import convspec
    from repro.models.cnn import resnet_like
    from repro.serve import AsyncServeFrontend, ServeRequest

    hw = 32 if reduced else 224
    shape = (hw, hw, 3)
    buckets = (1, 8)
    print(f"[B] serve resnet_like {hw}x{hw} through AsyncServeFrontend, "
          f"buckets {buckets}")
    model = resnet_like(image_shape=shape)
    params = model.init(jax.random.PRNGKey(SEED))
    fe = AsyncServeFrontend(model, params, {shape: buckets}, backend=backend)
    t0 = time.perf_counter()
    fe.warmup()
    print(f"[B] warmup {time.perf_counter() - t0:.1f}s (compile included)")
    progs = fe.programs[shape]
    for b in progs.buckets:
        gp = progs.graph_plan(b)
        print(f"[B] bucket {b}:\n{gp.explain()}")
        pallas = [n for n, p in gp.conv_plans.items()
                  if _is_pallas(p.algorithm)]
        _check(all(p.source != "fallback" for p in gp.conv_plans.values()),
               f"bucket {b}: a conv resolved through fallback")
        if backend is None and pallas:
            x = progs.put(np.zeros((b,) + shape, progs.input_dtype()))
            txt = progs.fn(b).lower(fe.params, x).compile().as_text()
            _check("tpu_custom_call" in txt,
                   f"bucket {b}: Pallas nodes {pallas} compiled without "
                   f"tpu_custom_call")
    convspec.reset_plan_stats()
    images = _requests(shape, 4 if reduced else 16, 2 if reduced else 8)
    for i, x in enumerate(images):
        fe.submit(ServeRequest(rid=i, images=x))
    done = sorted(fe.run(), key=lambda r: r.rid)
    resolutions = convspec.PLAN_STATS["resolutions"]
    _check([r.rid for r in done] == list(range(len(images))),
           "not every request came back exactly once")
    bad = [(r.rid, r.status) for r in done if r.status != "served"]
    _check(not bad, f"requests not served: {bad}")
    _check(resolutions == 0,
           f"{resolutions} plan() resolutions after warmup")
    st = fe.stats()
    print("[B] smoke reading, not a metric: "
          + json.dumps({"latency_ms": st.get("latency_ms"),
                        "batches": st.get("batches"),
                        "served": st.get("served")}, default=str))
    got = np.concatenate([r.out for r in done])
    ref = _lax_reference(model, params, images, max(buckets))
    err = _rel_err(got, ref)
    print(f"[B] {len(done)} requests / {len(got)} images served, 0 plan "
          f"resolutions after warmup, rel err vs forced lax {err:.3e}")
    _check(err <= TOL, f"served outputs: rel err {err:.3e} > {TOL}")
    print("[B] pass")


def phase_c(backend, reduced: bool) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_serve_mesh
    from repro.models.cnn import resnet_like
    from repro.serve import (CnnServeEngine, ImageRequest, ServeRequest,
                             ShardedServeDispatcher)

    hw = 32 if reduced else 224
    shape = (hw, hw, 3)
    bucket = (2,)
    _check(len(jax.devices()) >= 4,
           f"phase C needs 4 devices; found {len(jax.devices())}")
    print(f"[C] ShardedServeDispatcher over make_serve_mesh(4), "
          f"resnet_like {hw}x{hw}, per-shard bucket {bucket}")
    model = resnet_like(image_shape=shape)
    params = model.init(jax.random.PRNGKey(SEED))
    disp = ShardedServeDispatcher(model, params, {shape: bucket},
                                  mesh=make_serve_mesh(4), process_index=0,
                                  process_count=1, backend=backend)
    t0 = time.perf_counter()
    disp.warmup()
    print(f"[C] warmup {time.perf_counter() - t0:.1f}s (compile included)")
    images = _requests(shape, 4 if reduced else 12, 2 if reduced else 8)
    for i, x in enumerate(images):
        disp.submit(ServeRequest(rid=i, images=x))
    done = sorted(disp.run(), key=lambda r: r.rid)
    _check([r.rid for r in done] == list(range(len(images)))
           and all(r.status == "served" for r in done),
           "sharded dispatcher did not serve every request exactly once")
    eng = CnnServeEngine(model, params, shape, buckets=bucket,
                         backend=backend)
    for i, x in enumerate(images):
        eng.submit(ImageRequest(rid=i, images=x))
    ref = sorted(eng.run(), key=lambda r: r.rid)
    got = np.concatenate([r.out for r in done])
    want = np.concatenate([r.out for r in ref])
    _check(got.shape == want.shape,
           f"sharded output shape {got.shape} != engine {want.shape}")
    _check(np.array_equal(got, want),
           f"sharded outputs differ from the single-device engine "
           f"(max |diff| {np.abs(got - want).max():.3e})")
    print(f"[C] pass: {len(done)} requests / {len(got)} images bitwise "
          f"equal to the single-device CnnServeEngine")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only phase C (sharded serving)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="reduced sizes on the CPU, Pallas in interpret "
                         "mode; never reports ok")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    import jax

    cache = compile_cache.enable()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: no TPU (JAX found {device}); nothing was run",
              file=sys.stderr)
        return 2
    # the rehearsal plans for the TPU: Pallas kernels, run interpreted
    backend = "tpu" if args.cpu_rehearsal else None
    print(f"chip_smoke: device {device}, compile cache {cache}")
    from repro.core import autotune
    try:
        if args.chips == 4:
            phase_c(backend, args.cpu_rehearsal)
        else:
            phase_a(backend, args.cpu_rehearsal)
            phase_b(backend, args.cpu_rehearsal)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        fails = autotune.MEASURE_STATS["failures"]
        print(f"autotune failed candidates: {len(fails)}")
        for f in fails:
            print(f"  {json.dumps(f)}")
    if args.cpu_rehearsal:
        print("chip_smoke: rehearsal passed (CPU, reduced size; not a "
              "chip run)")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
