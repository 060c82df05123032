"""Model FLOP/s utilization of the whole bucket step (%) in the
``convnext_tiny`` cells, defined as ``mfu.bulk`` is: its reader, called
here."""
from pathlib import Path

from bench import run

_MFU = run.load_module(Path(__file__).with_name("mfu.bulk.py"),
                       "bench_metric_mfu_bulk")


def read(win):
    return _MFU.read(win)
