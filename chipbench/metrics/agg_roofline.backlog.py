"""The rounds' share of the HBM roofline, in %: the least bytes of the traced
rounds (each update read once, the global model read where FedSGD steps it,
the new model written once: ``roofline.least_bytes_per_round``) at the
chip's peak bandwidth, over the device-busy time of those rounds. A round
is bandwidth-bound (2 FLOP per 4 B read), so bytes bound it. Counts the
work, not the kernels: no implementation can read over 100%. Moves
``updates_per_s``."""


def read(w):
    busy = w.busy_s()
    if busy <= 0 or w.least_bytes <= 0:
        return None
    return 100.0 * w.least_bytes / w.hbm_bytes_per_s / busy
