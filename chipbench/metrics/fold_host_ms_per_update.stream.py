"""Host milliseconds inside the program's ``repro.fold`` spans per update
folded, in the open-loop stream cells, where each update is folded as it
arrives: moves ``round_latency_p50_ms``. None where the program opens no
such span."""
from chipbench import program_spans


def read(w):
    ms = program_spans.total_ms(w, "fold")
    if ms is None or w.n_updates == 0:
        return None
    return ms / w.n_updates
