"""The device's idle share of the traced rounds, in %: 1 - (union of the
device operations' intervals / the traced window). Moves ``updates_per_s``."""


def read(w):
    if w.window_s <= 0 or w.busy_s() <= 0:
        return None
    return 100.0 * (1.0 - w.busy_s() / w.window_s)
