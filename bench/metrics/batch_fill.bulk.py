"""Images over bucket slots, summed over the window's batches (%)."""


def read(win):
    slots = sum(b.bucket for b in win.batches)
    return 100.0 * sum(b.units for b in win.batches) / slots if slots else None
