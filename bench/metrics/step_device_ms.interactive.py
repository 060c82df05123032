"""Device busy time in the traced window over the batches dispatched
in it (ms per batch).  Every batch of the traced window, and no other,
runs on the device inside it: the loop starts the window with nothing
in flight and waits for every request before the window closes."""


def read(win):
    if win.trace is None or not win.batches:
        return None
    return win.trace.busy_s / len(win.batches) * 1e3
