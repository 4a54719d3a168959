"""Host milliseconds inside the program's ``repro.finish_round`` spans per
round, in the open-loop stream cells, where it follows a round's last fold
on the round's critical path: moves ``round_latency_p50_ms``. None where
the program opens no such span."""
from chipbench import program_spans


def read(w):
    ms = program_spans.total_ms(w, "finish_round")
    if ms is None or w.rounds == 0:
        return None
    return ms / w.rounds
