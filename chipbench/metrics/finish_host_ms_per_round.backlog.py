"""Host milliseconds inside the program's ``repro.finish_round`` spans
(``AggregationExecutor.finish_round``: ``FusionState.result``,
``FusionAlgorithm.apply``, ``publish_fused``) per round, in the closed-loop
backlog cells: moves ``updates_per_s``. None where the program opens no
such span."""
from chipbench import program_spans


def read(w):
    ms = program_spans.total_ms(w, "finish_round")
    if ms is None or w.rounds == 0:
        return None
    return ms / w.rounds
