"""Share of the traced window in which the device ran no operation
(%): 1 - busy / window, busy being the union of the operation
intervals on the device (``bench/trace.py``)."""


def read(win):
    if win.trace is None:
        return None
    return 100.0 * (1.0 - win.trace.busy_s / win.trace.window_s)
