"""The program's own host spans in a traced window.

The aggregator opens ``repro.drain``, ``repro.fold`` and
``repro.finish_round`` (``repro.obs.span``) on the thread that also holds
the harness's spans, so ``trace.load`` keeps them in ``TracedWindow.host``
under their full names, beside the runtime's events (``PjitFunction(...)``,
``DevicePut``) that nest inside them. A program older than its spans gives
none: each function here then returns None, and so does its reader.
"""
from __future__ import annotations

import bisect
from typing import List, Optional

from chipbench.trace import PREFIX as HARNESS
from chipbench.trace import Event, union

PREFIX = "repro."


def spans(w, name: str) -> List[Event]:
    """The program's spans called ``repro.<name>`` in the window."""
    return [e for e in w.host if e.name == PREFIX + name]


def total_ms(w, name: str) -> Optional[float]:
    """Host milliseconds inside ``repro.<name>``; None where there is none."""
    found = spans(w, name)
    if not found:
        return None
    return sum(e.end - e.start for e in found) / 1e6


def self_ms(w, name: str) -> Optional[float]:
    """Host milliseconds inside ``repro.<name>`` outside every runtime event
    nested in it (any event named neither ``repro.*`` nor ``chipbench.*``):
    per span, its duration less the union of those events' intervals,
    clipped to it. The Python the program runs itself, not the runtime's
    dispatch. None where there is no such span."""
    found = spans(w, name)
    if not found:
        return None
    runtime = sorted((e for e in w.host
                      if not e.name.startswith((PREFIX, HARNESS))),
                     key=lambda e: e.start)
    starts = [e.start for e in runtime]
    total = 0
    for s in found:
        inside = runtime[bisect.bisect_left(starts, s.start):
                         bisect.bisect_left(starts, s.end)]
        covered = union([(x.start, min(x.end, s.end)) for x in inside])
        total += (s.end - s.start) - sum(b - a for a, b in covered)
    return total / 1e6
