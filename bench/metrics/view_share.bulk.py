"""Share of the arrays put for the window's batches that were requests'
own arrays, put without a host copy (%): the sum of
``BatchTrace.view_slices`` over the sum of ``BatchTrace.slices``.
None where the program counts no slices."""


def read(win):
    slices = sum(getattr(b, "slices", 0) for b in win.batches)
    if not slices:
        return None
    return 100.0 * sum(b.view_slices for b in win.batches) / slices
