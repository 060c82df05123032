"""Share of the device's busy time spent in the glue of the conv and
dense graph nodes (%): the own time of every operation inside such a
node's ``jax.named_scope`` that is not the node's multiply-add kernel
(its Pallas kernel, or else XLA's convolution or dot) — layout copies,
pads, tile gathers and transforms, epilogues outside the kernel.

Read from the run's own profile (``bench/.state/trace``), inside the
traced window, with each operation's node taken from the ``op_name``
the trace keeps in its metadata (``bench/attribution.py``).  None where no
operation carries a node's scope, as on a program without per-node
scopes.
"""
from pathlib import Path

from bench import attribution, trace

TRACE_DIR = Path(__file__).resolve().parents[1] / ".state" / "trace"


def read(win):
    if win.trace is None or not win.batches or win.trace.busy_s <= 0:
        return None
    try:
        ops = attribution.load_device(trace.find_xplane(str(TRACE_DIR)))
    except FileNotFoundError:
        return None
    (window,) = attribution.tied_spans(
        ops, [(trace.WINDOW_SPAN, win.t0, win.t_end)],
        win.batches[0].transfer_t1)
    nodes = {n.name for work in win.work.values() for n in work}
    times = attribution.node_times(ops, window.start_ns, window.end_ns,
                                   nodes)
    if not set(times) - {attribution.UNSCOPED}:
        return None
    glue = sum(kg["glue"] for n, kg in times.items() if n in nodes)
    return 100.0 * glue / win.trace.busy_s
