#!/usr/bin/env python3
"""Read the compared number of a cell over many seeds, for the program
as configured and for a control run through the same path, each run a
short window at the cell's own load.

    python3 bench/tools/readings.py --workload resnet50_bulk \\
        --seeds 1,2,3 --control-seeds 4,5,6 --control int8 --seconds 3

``--control int8`` is the program's own int8 path (``QuantPolicy``,
calibrated on the pool), the control the limits are set against;
``--control bf16`` is its bfloat16 ``PrecisionPolicy``.  Each control
run keeps its calibration in a directory of its own, so no run merges
another's activation ranges.

One process; one JSON line per run on stdout.  The limits in the
configuration files are set from these readings (PERF.md gives them).
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
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", choices=("int8", "bf16"), default="int8")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run.setup_env()
    bench = run.load_benchmark()
    cell, config = run.find_cell(bench, args.workload)
    cfg = json.loads((run.ROOT / config["file"]).read_text())
    mix = json.loads((run.BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    device = run.check_device(int(cell["chips"]))
    peaks = run.load_peaks(device["kind"])
    run.enable_compile_cache()
    todo = [(int(s), None) for s in args.seeds.split(",") if s] + [
        (int(s), args.control) for s in args.control_seeds.split(",") if s]
    for seed, precision in todo:
        if precision is not None:
            os.environ["REPRO_CACHE_DIR"] = str(
                run.STATE / f"control-{precision}-{seed}")
        res = run.run_cell(cell, cfg, mix, seed, args.seconds, False, peaks,
                           device, precision=precision,
                           log=lambda *a, **k: None)
        print(json.dumps({
            "workload": cell["name"], "seed": seed,
            "precision": precision or cfg["dtype"],
            "logit_err": res["checks"]["logit_err"]["value"],
            "correct": res["correct"], "compared": res["compared_images"],
            "checks": {k: v["value"] for k, v in res["checks"].items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
