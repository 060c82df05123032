"""Tiny sizes of the benchmark's cells for CPU tests (the published
widths are for the chip)."""
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIGS = {
    "resnet50": dict(image_size=32, stem_channels=8,
                     stage_widths=[4, 8, 8, 8], stage_blocks=[1, 2, 1, 1],
                     num_classes=10),
    "squeezenet1_0": dict(image_size=48, conv1_channels=16, num_classes=10,
                          fires=[[4, 8, 8], [4, 8, 8], "pool", [8, 16, 16],
                                 "pool", [8, 16, 16]]),
}
MIXES = {"bulk": dict(pool_images=40),
         "interactive": dict(pool_images=24, rate_per_s=200)}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def cell(name):
    """``(cell, cfg, mix)`` of a BENCHMARK.json cell at a tiny size."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    c = {w["name"]: w for w in bench["workloads"]}[name]
    cfg = json.loads((BENCH / "configs" / f"{c['config']}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{c['traffic']}.json").read_text())
    return c, dict(cfg, **CONFIGS[c["config"]]), dict(mix,
                                                      **MIXES[c["traffic"]])


def run(name, seed, seconds=0.3, **kw):
    from bench import run as run_mod
    c, cfg, mix = cell(name)
    peaks = run_mod.load_peaks("TPU v5 lite")
    return run_mod.run_cell(c, cfg, mix, seed, seconds, False, peaks, CPU,
                            log=lambda *a, **k: None, **kw)
