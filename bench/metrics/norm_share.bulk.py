"""Share of the device's busy time spent in the LayerNorm nodes of
``convnext_tiny`` (``convnext_tiny.norm_nodes``) (%): all their
operations' own time in the traced window (``attribution.node_times``),
over the busy time.  None without a trace, or where no operation
carries a norm node's scope."""
import json
from pathlib import Path

from bench import window_nodes
from bench.configs import convnext_tiny

BENCH = Path(__file__).resolve().parents[1]
TRACE_DIR = BENCH / ".state" / "trace"
NODES = frozenset(convnext_tiny.norm_nodes(json.loads(
    (BENCH / "configs" / "convnext_tiny.json").read_text())))


def read(win):
    times = window_nodes.node_times(win, NODES, TRACE_DIR)
    if times is None:
        return None
    own = sum(kg["kernel"] + kg["glue"] for kg in times.values())
    return 100.0 * own / win.trace.busy_s
