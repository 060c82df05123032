"""Open-loop Poisson load generation + the multi-device scaling sweep.

Two records for BENCH_graph_serve.json (merged next to graph_serve's
via ``write_json(merge=True)``), both driving the DIST_SMOKE tiny_cnn
deployment through ``ShardedServeDispatcher`` (serve/distributed.py):

* ``serve/loadgen`` — an OPEN-LOOP load generator: arrivals are drawn
  from a Poisson process at each offered rate and submitted on
  schedule whether or not the dispatcher has caught up, so queueing
  delay is never masked by closed-loop back-pressure.  Sweeping the
  offered rate produces the latency-vs-offered-throughput curve: flat
  percentiles while capacity holds, then the knee where achieved
  throughput saturates and latency is queue depth.

* ``sharded_scaling`` — the subsystem's acceptance record: the same
  deployment driven to saturation over a serve mesh of every device
  count from 1 to ``len(jax.devices())``, all in this one process (a
  chip belongs to one process, so a child could not reach it),
  recording throughput, per-device utilization, and a SHA-1 digest over
  every output.  The digests assert the sharded results are
  bitwise-identical to the single-device ``CnnServeEngine`` at every
  device count.  On CPU, more devices come from
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before the
  process starts; there the scaling comes from the device-count-aware
  global buckets (per-shard bucket × mesh size) amortizing the fixed
  per-batch scheduling cost over more images.
"""
from __future__ import annotations

import argparse
import hashlib
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmarks.common import csv_row, write_json

#: the DIST_SMOKE geometry both records drive
SCALING_SHAPE: Tuple[int, int, int] = (8, 8, 3)


def _images(n: int, seed: int) -> np.ndarray:
    """The deterministic image pool: identical bytes at every device
    count, so output digests are comparable across device counts."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n,) + SCALING_SHAPE).astype(np.float32)


def _dispatcher(model, params, buckets, mesh=None):
    from repro.configs.serve import DIST_SMOKE
    from repro.serve import ShardedServeDispatcher
    return ShardedServeDispatcher(
        model, params, {SCALING_SHAPE: buckets}, mesh=mesh,
        process_index=0, process_count=1,
        max_wait_ms=DIST_SMOKE.max_wait_ms,
        default_deadline_ms=DIST_SMOKE.default_deadline_ms,
        pipeline_depth=DIST_SMOKE.pipeline_depth)


# ---------------------------------------------------------------------------
# one device count: throughput + digest measurement

def measure_mesh(n_devices: int, images: int, seed: int,
                 reps: int = 3) -> Dict:
    """Saturation throughput of the DIST_SMOKE deployment on a serve
    mesh of the first ``n_devices`` devices, plus bitwise evidence: a
    digest over the dispatcher's outputs (request order) and the same
    digest from the single-device synchronous engine on identical
    inputs.

    Throughput is the DRAIN rate: the backlog is queued first and only
    ``run()`` is timed — the server-side number an open-loop generator
    saturating the dispatcher would observe, with the client's submit
    cost off the clock.  Best of ``reps`` drains (single-core CI wall
    clocks are noisy); every rep must reproduce the same digest."""
    import jax

    from repro.configs.serve import DIST_SMOKE
    from repro.launch.mesh import make_serve_mesh
    from repro.models.cnn import tiny_cnn
    from repro.serve import CnnServeEngine, ImageRequest, ServeRequest

    buckets = DIST_SMOKE.geometry_map()[SCALING_SHAPE]
    model = tiny_cnn()
    params = model.init(jax.random.PRNGKey(0))
    imgs = _images(images, seed)

    disp = _dispatcher(model, params, buckets, make_serve_mesh(n_devices))
    disp.warmup()
    for i in range(8):                       # prime the dispatch path
        disp.submit(ServeRequest(rid=10**9 + i, images=imgs[i:i + 1]))
    disp.run()

    best_dt, digests, exactly_once = float("inf"), set(), True
    for rep in range(reps):
        base = rep * images
        for i in range(images):
            disp.submit(ServeRequest(rid=base + i, images=imgs[i:i + 1]))
        t0 = time.perf_counter()
        done = disp.run()
        best_dt = min(best_dt, time.perf_counter() - t0)
        done.sort(key=lambda r: r.rid)
        exactly_once &= (
            len(done) == images
            and [r.rid for r in done] == list(range(base, base + images))
            and all(r.status == "served" for r in done))
        outs = np.concatenate([r.out for r in done])
        digests.add(hashlib.sha1(outs.tobytes()).hexdigest())
    dt = best_dt
    st = disp.stats()

    # the single-device reference: same model/params/images through the
    # synchronous engine, unsharded, at the per-shard bucket sizes —
    # the per-shard batch shape every mesh device executes
    eng = CnnServeEngine(model, params, SCALING_SHAPE, buckets=buckets)
    eng.warmup()
    for i in range(images):
        eng.submit(ImageRequest(rid=i, images=imgs[i:i + 1]))
    ref = eng.run()
    ref.sort(key=lambda r: r.rid)
    ref_outs = np.concatenate([r.out for r in ref])

    return {
        "device_count": int(disp.n_devices),
        "global_buckets": list(disp.global_buckets(SCALING_SHAPE)),
        "images": images,
        "elapsed_ms": dt * 1e3,
        "img_per_s": images / dt,
        "exactly_once": exactly_once,
        # one digest per drain rep — a singleton set is determinism
        # evidence before it is compared across device counts
        "digest": sorted(digests)[0] if len(digests) == 1 else "UNSTABLE",
        "engine_digest": hashlib.sha1(ref_outs.tobytes()).hexdigest(),
        "per_device_utilization": [p["utilization"]
                                   for p in st["partitions"]],
        "batches": st["batches_by_program"],
    }


def scaling_record(images: int, seed: int = 0) -> Dict:
    import jax

    from repro.configs.serve import DIST_SMOKE
    runs = [measure_mesh(n, images, seed)
            for n in range(1, len(jax.devices()) + 1)]
    base = runs[0]["img_per_s"]
    digests = ({r["digest"] for r in runs}
               | {r["engine_digest"] for r in runs})
    return {
        "name": "sharded_scaling",
        "model": "tiny_cnn",
        "geometry": "x".join(map(str, SCALING_SHAPE)),
        "per_shard_buckets": list(DIST_SMOKE.geometry_map()[SCALING_SHAPE]),
        "images": images,
        "runs": runs,
        "speedups": {str(r["device_count"]): r["img_per_s"] / base
                     for r in runs},
        "bitwise_identical": len(digests) == 1,
        "exactly_once": all(r["exactly_once"] for r in runs),
    }


# ---------------------------------------------------------------------------
# open-loop Poisson curve (current process's devices)

def poisson_curve(rates: Sequence[float], duration_s: float,
                  seed: int = 0) -> Dict:
    import jax

    from repro.configs.serve import DIST_SMOKE
    from repro.models.cnn import tiny_cnn
    from repro.serve import ServeRequest

    model = tiny_cnn()
    params = model.init(jax.random.PRNGKey(0))
    disp = _dispatcher(model, params,
                       DIST_SMOKE.geometry_map()[SCALING_SHAPE])
    disp.warmup()
    pool = _images(64, seed)
    rng = np.random.default_rng(seed)
    rid, points = 0, []
    for rate in rates:
        n_req = max(16, int(rate * duration_s))
        telem = disp.frontend.telemetry
        start = len(telem.requests)
        # open loop: arrival times are fixed up front by the Poisson
        # process — a slow server gets further behind, not less traffic
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_req))
        t0 = time.perf_counter()
        k = 0
        while k < n_req:
            now = time.perf_counter() - t0
            while k < n_req and arrivals[k] <= now:
                disp.submit(ServeRequest(
                    rid=rid, images=pool[rid % len(pool)][None]))
                rid += 1
                k += 1
            disp.poll()
        disp.run()                           # drain the tail
        elapsed = time.perf_counter() - t0
        traces = telem.requests[start:]
        totals = [t.total_ms for t in traces if t.status == "served"]
        points.append({
            "offered_rps": float(rate),
            "achieved_rps": n_req / elapsed,
            "requests": n_req,
            "p50_ms": float(np.percentile(totals, 50)),
            "p95_ms": float(np.percentile(totals, 95)),
            "p99_ms": float(np.percentile(totals, 99)),
            "deadline_misses": sum(1 for t in traces
                                   if t.status != "served"),
        })
    return {
        "name": "serve/loadgen",
        "model": "tiny_cnn",
        "geometry": "x".join(map(str, SCALING_SHAPE)),
        "devices": int(disp.n_devices),
        "duration_s": duration_s,
        "points": points,
    }


# ---------------------------------------------------------------------------

def run(quick: bool = True) -> List[str]:
    rates = (250.0, 1000.0, 4000.0) if quick else (
        250.0, 500.0, 1000.0, 2000.0, 4000.0, 8000.0)
    curve = poisson_curve(rates, duration_s=0.5 if quick else 1.0)
    # a deep backlog (≈1-2s of queueing at capacity) keeps every drain
    # in the saturated regime the scaling claim is about
    scaling = scaling_record(images=4096)

    rows = []
    for p in curve["points"]:
        rows.append(csv_row(
            f"serve/loadgen_r{int(p['offered_rps'])}",
            p["p95_ms"] * 1e3,
            f"achieved_rps={p['achieved_rps']:.0f} "
            f"p50_ms={p['p50_ms']:.2f}"))
    for r in scaling["runs"]:
        n = r["device_count"]
        rows.append(csv_row(
            f"serve/sharded_scaling_d{n}",
            1e6 / r["img_per_s"],
            f"img_per_s={r['img_per_s']:.0f} "
            f"speedup={scaling['speedups'][str(n)]:.2f} "
            f"bitwise={'ok' if scaling['bitwise_identical'] else 'FAIL'}"))
    write_json("graph_serve", [curve, scaling], merge=True)
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    for row in run(quick=not args.full):
        print(row)


if __name__ == "__main__":
    main()
