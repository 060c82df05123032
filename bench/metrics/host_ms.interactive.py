"""Median over the window's batches of the frontend's own host work on
a batch: its ``frontend.pack``, ``put``, ``launch``, ``fetch`` and
``scatter`` spans, on the frontend's clock (ms).  The wait on the
device (``frontend.wait``) is left out.  None where the program stamps
no such spans."""
from bench.stats import percentile

STAGES = ("pack", "put", "launch", "fetch", "scatter")


def read(win):
    ms = []
    for b in win.batches:
        parts = ([b.stage_ms(s) for s in STAGES]
                 if hasattr(b, "stage_ms") else [None])
        if None not in parts:
            ms.append(sum(parts))
    return percentile(ms, 50) if ms else None
