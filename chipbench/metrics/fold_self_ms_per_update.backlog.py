"""Host milliseconds a ``repro.fold`` span spends outside the runtime events
nested in it, per update folded: the Python glue of ``FusionState.fold`` and
``ops.accumulate`` (tree walks, per-leaf wrappers), not the dispatches
(``PjitFunction(...)``, ``DevicePut``) it makes. In the closed-loop backlog
cells: moves ``updates_per_s``. None where the program opens no such span."""
from chipbench import program_spans


def read(w):
    ms = program_spans.self_ms(w, "fold")
    if ms is None or w.n_updates == 0:
        return None
    return ms / w.n_updates
