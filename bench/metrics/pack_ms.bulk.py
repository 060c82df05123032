"""Mean ``frontend.pack`` span over the window's batches: the frontend
packing a batch's units into one host array, on its own clock (ms).
None where the program stamps no pack span."""


def read(win):
    ms = [b.stage_ms("pack") for b in win.batches if hasattr(b, "stage_ms")]
    ms = [m for m in ms if m is not None]
    return sum(ms) / len(ms) if ms else None
