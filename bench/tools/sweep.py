#!/usr/bin/env python3
"""Find the knee of an open-loop cell: serve its mix at each offered
rate, one process and one warm frontend, and report for each rate the
latency, how late the generator ran and whether a backlog grew.

    python3 bench/tools/sweep.py --workload resnet50_interactive \\
        --seed 5 --seconds 10 --rates 100,150,200,250

A rate is sustained when the requests of the window's last quarter
wait, on the median, no more than twice as long as those of its first
quarter, and the backlog left at the window's close drains within 5%
of the window.  The rates are swept in the order given and the sweep stops at
the first one not sustained; the knee is the highest sustained rate
before it, and the cell's mix takes 0.8 of it.  Writes one JSON line
per rate to stdout, then ``{"knee_per_s": ...}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    run.setup_env()
    from bench.stats import percentile
    bench = run.load_benchmark()
    cell, config = run.find_cell(bench, args.workload)
    cfg = json.loads((run.ROOT / config["file"]).read_text())
    mix = json.loads((run.BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    device = run.check_device(int(cell["chips"]))
    run.enable_compile_cache()
    c = run.Cell(cell, cfg, mix, args.seed)
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        sent, batches, _, t0, t_end = c.serve(
            args.seconds, mix=dict(mix, rate_per_s=rate))
        lat = [(s.t_done - s.t_sched) * 1e3 for s in sent]
        q = max(1, len(sent) // 4)
        first = percentile(lat[:q], 50)
        last = percentile(lat[-q:], 50)
        overrun = t_end - t0 - args.seconds
        sustained = last <= 2 * first and overrun <= 0.05 * args.seconds
        print(json.dumps({
            "rate_per_s": rate, "requests": len(sent),
            "served": sum(s.status == "served" for s in sent),
            "latency_p50_ms": percentile(lat, 50),
            "latency_p95_ms": percentile(lat, 95),
            "gen_lag_p95_ms": percentile(
                [(s.t_submit - s.t_sched) * 1e3 for s in sent], 95),
            "first_quarter_p50_ms": first, "last_quarter_p50_ms": last,
            "sustained": sustained,
            "batches": len(batches),
            "images_per_batch": sum(b.units for b in batches) / len(batches),
            "overrun_s": overrun,
            "device": device}), flush=True)
        if not sustained:
            break
        knee = rate
    print(json.dumps({"knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
