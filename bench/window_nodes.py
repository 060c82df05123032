"""Device time of named graph nodes inside a traced window, for the
per-layer readers that charge time to some of a configuration's nodes.

It reads the run's own profile as ``glue_share.bulk`` does: the window
tied to the trace's clock at the window's first batch, each operation
charged to its node by the ``op_name`` in its metadata
(``attribution.node_times``).
"""
from __future__ import annotations

from pathlib import Path
from typing import Container, Dict, Optional

from bench import attribution, trace


def node_times(win, nodes: Container[str], trace_dir: Path
               ) -> Optional[Dict[str, Dict[str, float]]]:
    """``{node: {"kernel": s, "glue": s}}`` over ``nodes`` in the
    window, or None without a trace, a profile or a node's scope."""
    if win.trace is None or not win.batches or win.trace.busy_s <= 0:
        return None
    try:
        ops = attribution.load_device(trace.find_xplane(str(trace_dir)))
    except FileNotFoundError:
        return None
    (window,) = attribution.tied_spans(
        ops, [(trace.WINDOW_SPAN, win.t0, win.t_end)],
        win.batches[0].transfer_t1)
    times = attribution.node_times(ops, window.start_ns, window.end_ns,
                                   nodes)
    found = {n: kg for n, kg in times.items() if n in nodes}
    return found or None
