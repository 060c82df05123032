"""Share of the device's busy time that the conv and dense work of the
traced window would take at the roofline (%).

Each node of the graph before fusion, at each batch's bucket size, is
bounded below by max(flops / peak, bytes / HBM bandwidth)
(``bench/work.py``); the sum over the window's batches is divided by
the union of the device's operation intervals (``bench/trace.py``).
The peak is the bf16 one: at XLA's default precision the MXU takes
float32 operands as single bf16 passes.  Padded slots count as work,
since the device computes them.  The busy time also holds the work of
pools, pads, concats and layout changes, so this is the conv and dense
work's share of all device time, not one kernel's share of its own
time.
"""


def read(win):
    if win.trace is None or not win.batches or win.trace.busy_s <= 0:
        return None
    peak_f = win.peaks["bf16_flops_per_s"]
    peak_b = win.peaks["hbm_bytes_per_s"]
    bound = sum(n.min_seconds(peak_f, peak_b)
                for b in win.batches for n in win.work[b.geometry, b.bucket])
    return 100.0 * bound / win.trace.busy_s
