"""Share of the traced window, in %, in which no operation ran on the
device: 1 minus the union of the device's operation intervals over the
window, averaged over the cell's chips (profiler trace)."""


def read(rec):
    tr = rec.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
