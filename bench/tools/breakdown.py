#!/usr/bin/env python3
"""Serve one traced window of a cell and say where its time went, by the
program's own names: idle gaps labelled by the frontend's host spans,
device time per graph node split into kernel and glue, and the clock
tie between each batch's launch and its program on the device.

    python3 bench/tools/breakdown.py --workload resnet50_bulk \\
        --seed 7 --seconds 10

The window is traced as ``bench/run.py --trace 1`` traces it (the
profiler's host tracer off; the loop's and the frontend's spans tied to
the device trace at the first program), and the cell's metrics, end
to end and per layer, are read from it.

One process; one JSON line on stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run.setup_env()
    import jax

    from bench import attribution, trace
    bench = run.load_benchmark()
    cell, config = run.find_cell(bench, args.workload)
    cfg = json.loads((run.ROOT / config["file"]).read_text())
    mix = json.loads((run.BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    device = run.check_device(int(cell["chips"]))
    peaks = run.load_peaks(device["kind"])
    run.enable_compile_cache()
    c = run.Cell(cell, cfg, mix, args.seed, log=lambda *a, **k: None)
    trace_dir = run.STATE / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    spans = run.HostSpans()
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    sent, batches, requests, t0, t_end = c.serve(args.seconds, spans=spans)
    jax.profiler.stop_trace()

    ops = attribution.load_device(trace.find_xplane(str(trace_dir)))
    anchor = batches[0].transfer_t1
    loop = attribution.tied_spans(
        ops, [(trace.WINDOW_SPAN, t0, t_end)] + spans.spans, anchor)
    program = attribution.tied_spans(
        ops, c.fe.telemetry.spans(batches, requests), anchor)
    summary = trace.summarize(ops + loop)
    win = run.Window(args.seconds, t0, 0.0, sent, batches, requests,
                     summary, c.work, peaks, t_end)

    nodes = {n.name for work in c.work.values() for n in work} | {
        n.name for progs in c.fe.programs.values() for b in progs.buckets
        for n in progs.graph_plan(b).graph.nodes}
    window = next(e for e in loop if e.name == trace.WINDOW_SPAN)
    times = attribution.node_times(ops, window.start_ns, window.end_ns,
                                   nodes)
    conv = {n.name for work in c.work.values() for n in work}
    split = dict.fromkeys(("conv_dense_kernel_s", "conv_dense_glue_s",
                           "other_nodes_s", "unscoped_s"), 0.0)
    for n, kg in times.items():
        if n in conv:
            split["conv_dense_kernel_s"] += kg["kernel"]
            split["conv_dense_glue_s"] += kg["glue"]
        else:
            split["unscoped_s" if n == attribution.UNSCOPED
                  else "other_nodes_s"] += kg["kernel"] + kg["glue"]
    out = {
        "workload": cell["name"], "seed": args.seed,
        "device": device, "batches": len(batches),
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "metrics": {k: v["value"] for k, v in run.read_metrics(
            bench["end_to_end"] + bench["per_layer"], cell["name"],
            win).items() if k != "setup_s"},
        "idle_gaps": [[label, s, {n: round(f, 3) for n, f in sorted(
            cover.items(), key=lambda kv: -kv[1])[:4]}]
            for label, s, cover in attribution.label_gaps(ops + loop
                                                          + program)],
        "idle_gaps_loop": [list(g) for g in summary.idle_gaps],
        "nodes": [list(r) for r in attribution.top_nodes(times)],
        **split,
        "attributed_over_busy": sum(split.values()) / summary.busy_s,
        "tie": attribution.launch_ties(ops + loop + program),
        "batch_ms": c.fe.telemetry.batch_ms(),
        "device_ops": summary.device_ops,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
