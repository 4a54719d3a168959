"""Host milliseconds inside the program's ``repro.fold`` spans
(``FusionState.fold`` -> ``kernels/ops.accumulate``'s per-leaf dispatch) per
update folded, in the closed-loop backlog cells: moves ``updates_per_s``.
None where the program opens no such span."""
from chipbench import program_spans


def read(w):
    ms = program_spans.total_ms(w, "fold")
    if ms is None or w.n_updates == 0:
        return None
    return ms / w.n_updates
