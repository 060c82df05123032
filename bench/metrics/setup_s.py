"""Process start to the window's start: device init, weights, image
pools, bucket programs built (from the compile cache once a checkout
has compiled them) and warm traffic (s)."""


def read(win):
    return win.setup_s
