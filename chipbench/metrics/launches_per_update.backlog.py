"""Device programs launched in the traced rounds per update: the dispatch
count of ``kernels/ops``, ``finish_round`` spread over the round's updates.
A count, so it repeats exactly. Moves ``updates_per_s``."""


def read(w):
    if w.n_updates == 0 or w.launches() == 0:
        return None
    return w.launches() / w.n_updates
