"""The depthwise convs' share of their roofline (%): the roofline bound
of every depthwise node of ``convnext_tiny`` (``convnext_tiny.dw_nodes``)
at each batch of the traced window, max(flops / peak, bytes / HBM
bandwidth) from ``bench/work.py``, over the time the nodes' kernels
took.  A node's kernel is the ``depthwise_tap`` Pallas kernel where it
is planned, else XLA's convolution (``attribution.node_times``).  None
without a trace, or where no operation carries a depthwise node's
scope."""
import json
from pathlib import Path

from bench import window_nodes
from bench.configs import convnext_tiny

BENCH = Path(__file__).resolve().parents[1]
TRACE_DIR = BENCH / ".state" / "trace"
NODES = frozenset(convnext_tiny.dw_nodes(json.loads(
    (BENCH / "configs" / "convnext_tiny.json").read_text())))


def read(win):
    times = window_nodes.node_times(win, NODES, TRACE_DIR)
    kernel = sum(kg["kernel"] for kg in (times or {}).values())
    if kernel <= 0:
        return None
    peak_f = win.peaks["bf16_flops_per_s"]
    peak_b = win.peaks["hbm_bytes_per_s"]
    bound = sum(n.min_seconds(peak_f, peak_b)
                for b in win.batches for n in win.work[b.geometry, b.bucket]
                if n.name in NODES)
    return 100.0 * bound / kernel
