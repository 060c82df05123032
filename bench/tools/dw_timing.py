#!/usr/bin/env python3
"""Time the depthwise convs of ConvNeXt-T on the chip: XLA's grouped
convolution (the ``lax`` executor) against the ``depthwise_tap`` kernel
under its launch configs, per shape and batch.

    python3 bench/tools/dw_timing.py --buckets 8,32

Each case runs ``--chain`` convs back to back in one program (the
output of one is the input of the next), so a reading is device time
per conv, not dispatch; the median of ``--repeats`` programs is kept.
Each kernel output is also checked against ``lax`` at ``HIGHEST``
precision.  One JSON line per case on stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

#: (H, C) of the 7x7 depthwise convs of ConvNeXt-T's four stages
SHAPES = ((56, 96), (28, 192), (14, 384), (7, 768))


def _timed(fn, x, repeats):
    import jax
    jax.block_until_ready(fn(x))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def _configs(ex, spec, default):
    """Launch configs worth timing, the executor's own candidates among
    them: over ``rows`` (1, 2, 4, 7) and ``tc`` (C, 128, 256) the one
    ``nb`` its cost model prefers, and every ``nb`` at the default's
    ``rows`` and ``tc``."""
    from repro.core.executors import LaunchConfig
    n, _, _, c = spec.in_shape
    oh = spec.out_shape[1]
    best = {}
    for rows in (r for r in (1, 2, 4, 7) if oh % r == 0):
        for tc in (c,) + tuple(t for t in (128, 256)
                               if t < c and c % t == 0):
            for nb in (d for d in (1, 2, 4, 8, 16, 32) if n % d == 0):
                cfg = LaunchConfig.of({"nb": nb, "rows": rows, "tc": tc})
                if not ex.config_supports(spec, cfg)[0]:
                    continue
                key = (rows, tc)
                if key == (default["rows"], default["tc"]):
                    best[key + (nb,)] = cfg
                elif key not in best or (ex.config_cost(spec, cfg)
                                         < ex.config_cost(spec, best[key])):
                    best[key] = cfg
    return list(best.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--buckets", default="8,32")
    ap.add_argument("--chain", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import convspec, executors
    ex = executors.get("depthwise_tap")
    rng = np.random.default_rng(0)
    for n in map(int, args.buckets.split(",")):
        for h, c in SHAPES:
            spec = convspec.ConvSpec((n, h, h, c), (7, 7, 1, c), (1, 1),
                                     (3, 3), "float32", "bias", c)
            x = jnp.asarray(rng.standard_normal(spec.in_shape), jnp.float32)
            # weights that keep a chain of convs O(1)
            w = jnp.asarray(rng.standard_normal((7, 7, 1, c)) / 7,
                            jnp.float32)
            b = jnp.asarray(0.05 * rng.standard_normal(c), jnp.float32)
            want = jax.lax.conv_general_dilated(
                x, w, (1, 1), ((3, 3), (3, 3)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=c,
                precision=jax.lax.Precision.HIGHEST) + b
            default = ex.default_config(spec)
            cands = [("lax", executors.LaunchConfig())] + [
                ("depthwise_tap", cfg) for cfg in _configs(ex, spec, default)]
            for algo, cfg in cands:
                p = convspec.ConvPlan(spec, algo, "forced", "timing", "tpu",
                                      config=cfg)

                def chain(x, p=p):
                    return jax.lax.fori_loop(0, args.chain,
                                             lambda i, y: p(y, w, b), x)
                try:
                    err = float(jnp.abs(jax.jit(p)(x, w, b) - want).max())
                    t = _timed(jax.jit(chain), x, args.repeats)
                except Exception as e:      # a config Mosaic refuses
                    print(json.dumps({"n": n, "h": h, "c": c, "algo": algo,
                                      "config": cfg.key(),
                                      "error": str(e)[:300]}), flush=True)
                    continue
                print(json.dumps({
                    "n": n, "h": h, "c": c, "algo": algo,
                    "config": cfg.key(), "default": cfg == default,
                    "ms": 1e3 * t / args.chain, "max_err": err}),
                    flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
