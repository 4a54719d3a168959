"""Device-busy milliseconds in the traced window per update folded, in the
open-loop stream cells. Moves ``round_latency_p50_ms``."""


def read(w):
    busy = w.busy_s()
    if w.n_updates == 0 or busy <= 0:
        return None
    return 1e3 * busy / w.n_updates
