#!/usr/bin/env python3
"""Time the host-to-device feed of one served batch on the chip, at the
bulk cells' input (224x224x3 float32).

    python3 bench/tools/put_timing.py --images 32 --slice 8

Every case is the median of ``--repeats``, in ms, over views of a pool
of images made on the host as ``bench/run.py`` makes it:

* ``pack``: ``np.concatenate`` of ``--images`` pool images, in
  ``--slice``-image views, into one batch (the frontend's host pack);
* ``packed_put``: one ``jax.device_put`` of the packed batch, the call's
  return (``return_ms``) and ``block_until_ready`` (``ready_ms``);
* ``list_put``: one ``jax.device_put`` of the list of slice views;
* ``list_put_busy``: the same while the device runs a program of about
  ``--busy-ms`` (the feed overlaps the previous batch's compute);
* ``thread_put``: the list put from a second thread while this thread
  counts a Python loop.  ``loop_share`` is the loop's rate against the
  same loop while the other thread sleeps: near 1 when the runtime
  releases the GIL during the copy.  ``start_ms`` is how long the
  thread waited to start its put (the GIL's hand-over);
* ``assemble``: one call of a jitted step that concatenates the slices
  on the device into one batch, launch to ready;
* ``packed_put_n``: the packed put at 1 to ``--images`` images, the
  curve a threshold on the feed path's batch bytes is read from.

One JSON line per case on stdout, each naming the device.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import statistics
import sys
import time

#: the bulk cells' image (H, W, C)
IMAGE = (224, 224, 3)


def _med(xs):
    return statistics.median(xs) * 1e3


def _put_times(put, make, repeats):
    """Median ms of ``put(make())``'s return and of its readiness."""
    import jax
    ret, ready = [], []
    for i in range(repeats):
        x = make(i)
        t0 = time.perf_counter()
        y = put(x)
        t1 = time.perf_counter()
        jax.block_until_ready(y)
        t2 = time.perf_counter()
        ret.append(t1 - t0)
        ready.append(t2 - t0)
        del y
    return {"return_ms": _med(ret), "ready_ms": _med(ready)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--images", type=int, default=32)
    ap.add_argument("--slice", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=21)
    ap.add_argument("--pool", type=int, default=256)
    ap.add_argument("--busy-ms", type=float, default=15.0)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    n, s = args.images, args.slice
    if n % s:
        raise SystemExit(f"--slice {s} must divide --images {n}")
    pool = np.random.default_rng(0).standard_normal(
        (args.pool,) + IMAGE, dtype=np.float32)

    def views(i, k=n):
        """The ``i``-th batch of ``k`` images as ``--slice``-image views,
        round-robin over the pool."""
        first = (i * k) % (args.pool - k + 1)
        return [pool[a:a + min(s, first + k - a)]
                for a in range(first, first + k, s)]

    def emit(case, **kw):
        print(json.dumps({"case": case, "images": n, "slice": s,
                          "device": device, **kw}), flush=True)

    reps = args.repeats
    jax.block_until_ready(jax.device_put(views(0)))     # runtime warm

    packs = []
    for i in range(reps):
        v = views(i)
        t0 = time.perf_counter()
        np.concatenate(v)
        packs.append(time.perf_counter() - t0)
    emit("pack", ms=_med(packs))
    emit("packed_put", **_put_times(
        jax.device_put, lambda i: np.concatenate(views(i)), reps))
    emit("list_put", **_put_times(jax.device_put, views, reps))

    # a device program of about --busy-ms: a chain of matmuls
    a = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2048, 2048), dtype=np.float32) / 45.0)

    def chain(x, steps):
        return jax.lax.fori_loop(0, steps, lambda _, y: jnp.tanh(y @ a), x)
    busy = jax.jit(chain, static_argnums=1)
    jax.block_until_ready(busy(a, 1))
    t0 = time.perf_counter()
    jax.block_until_ready(busy(a, 64))
    per_step = (time.perf_counter() - t0) / 64
    steps = max(1, int(args.busy_ms / 1e3 / max(per_step, 1e-6)))
    jax.block_until_ready(busy(a, steps))
    ret, ready, prog = [], [], []
    for i in range(reps):
        t0 = time.perf_counter()
        z = busy(a, steps)
        t1 = time.perf_counter()
        y = jax.device_put(views(i))
        t2 = time.perf_counter()
        jax.block_until_ready(y)
        t3 = time.perf_counter()
        jax.block_until_ready(z)
        prog.append(time.perf_counter() - t0)
        ret.append(t2 - t1)
        ready.append(t3 - t1)
    emit("list_put_busy", return_ms=_med(ret), ready_ms=_med(ready),
         program_ms=_med(prog))

    ex = concurrent.futures.ThreadPoolExecutor(max_workers=1)

    def put_job(v):
        t0 = time.perf_counter()
        y = jax.device_put(v)
        t1 = time.perf_counter()
        jax.block_until_ready(y)
        return t0, t1, time.perf_counter()

    def spin(fut):
        k = 0
        while not fut.done():
            k += 1
        return k
    ex.submit(put_job, views(0)).result()
    start, ret, ready, share = [], [], [], []
    for i in range(reps):
        t_sub = time.perf_counter()
        fut = ex.submit(put_job, views(i))
        k = spin(fut)
        t_end = time.perf_counter()
        t0, t1, t2 = fut.result()
        rate = k / (t_end - t_sub)
        t_sub2 = time.perf_counter()
        k2 = spin(ex.submit(time.sleep, t2 - t0))
        rate_alone = k2 / (time.perf_counter() - t_sub2)
        start.append(t0 - t_sub)
        ret.append(t1 - t0)
        ready.append(t2 - t0)
        share.append(rate / rate_alone)
    ex.shutdown()
    emit("thread_put", start_ms=_med(start), return_ms=_med(ret),
         ready_ms=_med(ready), loop_share=statistics.median(share))

    assemble = jax.jit(lambda xs: jnp.concatenate(xs, axis=0))
    xs = jax.device_put(views(0))
    jax.block_until_ready(assemble(xs))
    t_call = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(assemble(xs))
        t_call.append(time.perf_counter() - t0)
    emit("assemble", call_ms=_med(t_call))

    for m in sorted({m for m in (1, 2, 4, 8, 16, 24) if m < n} | {n}):
        emit("packed_put_n", n_images=m,
             mbytes=m * int(np.prod(IMAGE)) * 4 / 1e6,
             **_put_times(jax.device_put,
                          lambda i, m=m: np.ascontiguousarray(
                              pool[(i * m) % (args.pool - m + 1):][:m]),
                          reps))
    return 0


if __name__ == "__main__":
    sys.exit(main())
