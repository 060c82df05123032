"""Model FLOP/s utilization of the whole bucket step (%): the conv and
dense FLOPs of one image (``bench/work.py``, from shapes) times the
images served in the traced window, over its length and the chip's
bf16 peak.  Padded slots do not count."""


def read(win):
    if win.trace is None or not win.batches:
        return None
    per_image = {k: sum(n.flops for n in w) / k[1]
                 for k, w in win.work.items()}
    flops = sum(b.units * per_image[b.geometry, b.bucket]
                for b in win.batches)
    return 100.0 * flops / win.trace.window_s / win.peaks["bf16_flops_per_s"]
