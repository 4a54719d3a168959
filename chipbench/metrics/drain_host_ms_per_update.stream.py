"""Host milliseconds inside ``AggregationExecutor.drain`` per update drained,
in the open-loop stream cells, where each update is drained as it arrives:
moves ``round_latency_p50_ms``."""


def read(w):
    if w.n_updates == 0:
        return None
    return 1e3 * w.span_s("drain") / w.n_updates
