"""Device idle share: 1 minus the union of device-busy intervals, over the traced window (%)."""


def read(reduced, units):
    if reduced.window_s <= 0 or reduced.busy_s <= 0:
        return None
    return 100.0 * (1.0 - reduced.busy_s / reduced.window_s)
