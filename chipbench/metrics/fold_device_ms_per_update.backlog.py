"""Device milliseconds of the fold's programs per update folded, in the
closed-loop backlog cells: the ``first_fold`` and ``fold_into`` launches
(``kernels/ops.py``) on the trace's ``XLA Modules`` line, averaged over the
chips traced. The update's concat and ``pair_fuse``, without the finish.
Moves ``updates_per_s``. None where the trace holds no such program."""

#: the fold's programs, as the trace names their launches (``jit_<name>``)
PROGRAMS = ("jit_first_fold", "jit_fold_into")


def read(w):
    if w.n_updates == 0 or not w.programs:
        return None
    ns = sum(x.end - x.start for evs in w.programs.values() for x in evs
             if x.name.split("(")[0] in PROGRAMS)
    if ns <= 0:
        return None
    return ns / len(w.programs) / 1e6 / w.n_updates
