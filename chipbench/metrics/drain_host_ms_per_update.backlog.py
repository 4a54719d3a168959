"""Host milliseconds inside ``AggregationExecutor.drain`` per update drained
(fl/aggregator.drain -> fl/fusion.fold -> kernels/ops.accumulate), in the
closed-loop backlog cells: moves ``updates_per_s``."""


def read(w):
    if w.n_updates == 0:
        return None
    return 1e3 * w.span_s("drain") / w.n_updates
