"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  table1_inventory    paper Table 1 (CNN conv config inventory)
  paper_figures       paper Figures 5/6/7 (speedup vs best library conv)
  table345_breakdown  paper Tables 3/4/5 (per-kernel time split)
  graph_serve         graph-planned CNN programs + batch-bucketed serving
  loadgen             open-loop Poisson curves + multi-device scaling sweep
  lm_substrate        framework-layer micro-benchmarks

``--full`` sweeps every distinct config (slow on 1 CPU core);
the default quick set covers every profiled configuration of the paper.
Roofline terms for the assigned architectures come from the dry-run
artifacts (python -m repro.roofline.analysis), not from here.
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    args = ap.parse_args(argv)
    quick = not args.full
    from repro.launch import compile_cache
    compile_cache.enable()

    from benchmarks import (graph_serve, lm_substrate, loadgen,
                            paper_figures, table1_inventory,
                            table345_breakdown)
    mods = {
        "table1_inventory": table1_inventory,
        "paper_figures": paper_figures,
        "table345_breakdown": table345_breakdown,
        "graph_serve": graph_serve,
        "loadgen": loadgen,
        "lm_substrate": lm_substrate,
    }
    names = args.only.split(",") if args.only else list(mods)
    print("name,us_per_call,derived")
    for name in names:
        for row in mods[name].run(quick=quick):
            print(row)
        sys.stdout.flush()


if __name__ == "__main__":
    main()
