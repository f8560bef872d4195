"""Share of the traced window in which no operation runs on a device,
averaged over the cell's devices."""


def read(r):
    return 100.0 * (1.0 - r.busy_s / r.window_s)
