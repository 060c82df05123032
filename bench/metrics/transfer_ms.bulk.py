"""Mean ``BatchTrace.transfer_ms`` (``device_put`` of the packed batch
until it is on the device) over the window's batches (ms)."""


def read(win):
    t = [b.transfer_ms for b in win.batches]
    return sum(t) / len(t) if t else None
