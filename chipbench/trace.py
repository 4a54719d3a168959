"""From the profiler's trace to what the per-layer metrics read.

The trace (``*.xplane.pb``, read with ``jax.profiler.ProfileData``) holds
the harness's host spans (``chipbench.round``, ``.publish``, ``.drain``,
``.finish_round``, ``.wait``, each with its round) and, on each TPU core's
plane, one event per program launched (line ``XLA Modules``) and one per
operation it ran (line ``XLA Ops``). Both are on the profiler's clock.

``window`` cuts the trace to the traced rounds: from the first round span's
start to the last one's end. Device busy time is the union of the
operations' intervals in that window; idle is the rest of it.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

PREFIX = "chipbench."
DEVICE_PLANE = "/device:TPU:"
PROGRAM_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int  # ns, profiler clock
    end: int
    stats: Tuple[Tuple[str, object], ...] = ()

    def stat(self, key, default=None):
        return dict(self.stats).get(key, default)


@dataclasses.dataclass
class Trace:
    spans: List[Event]               # host spans named chipbench.*
    programs: Dict[str, List[Event]]  # device plane -> program launches
    ops: Dict[str, List[Event]]      # device plane -> operations
    host: List[Event] = dataclasses.field(default_factory=list)
    #  every event on the host thread that holds the spans: the dispatches
    #  (``PjitFunction(...)``, ``DevicePut``) inside them


def _event(e, stats=False) -> Event:
    return Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                 tuple(e.stats) if stats else ())


def load(path: Path) -> Trace:
    """Read one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans: List[Event] = []
    host: List[Event] = []
    programs: Dict[str, List[Event]] = defaultdict(list)
    ops: Dict[str, List[Event]] = defaultdict(list)
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name in (PROGRAM_LINE, OP_LINE):
                dest = (programs if line.name == PROGRAM_LINE else ops)
                dest[plane.name].extend(_event(e) for e in line.events)
            elif not device:
                events = list(line.events)
                ours = [_event(e, stats=True) for e in events
                        if e.name.startswith(PREFIX)]
                if ours:
                    spans += [dataclasses.replace(e, name=e.name[len(PREFIX):])
                              for e in ours]
                    host += [_event(e) for e in events]
    return Trace(spans, dict(programs), dict(ops), host)


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge overlapping [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


@dataclasses.dataclass
class TracedWindow:
    """The traced rounds, as the per-layer metrics read them."""

    start: int
    end: int
    rounds: int
    n_updates: int
    spans: List[Event]
    programs: Dict[str, List[Event]]
    ops: Dict[str, List[Event]]
    least_bytes: int    # of the traced rounds (roofline.least_bytes_per_round)
    hbm_bytes_per_s: float
    host: List[Event] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.end - self.start) / 1e9

    def busy_intervals(self, plane: str) -> List[Tuple[int, int]]:
        return union(_clip(self.ops.get(plane, []), self.start, self.end))

    def busy_s(self) -> float:
        """Device-busy seconds, averaged over the chips traced."""
        if not self.ops:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(p))
                   for p in self.ops) / len(self.ops) / 1e9

    def launches(self) -> int:
        """Programs launched on the device, summed over the chips."""
        return sum(len(v) for v in self.programs.values())

    def span_s(self, name: str) -> float:
        """Host seconds inside spans called ``name``."""
        return sum(e.end - e.start for e in self.spans if e.name == name) / 1e9

    def breakdown(self, top: int = 10) -> dict:
        """The programs that took most device time, and the device's idle
        time by what the host was doing in it: at each gap's middle, the
        harness span and the innermost event on the host thread (a
        dispatch such as ``PjitFunction(pair_fuse)``), or
        ``between_rounds``."""
        prog_time: Dict[str, float] = defaultdict(float)
        for evs in self.programs.values():
            for x in evs:
                prog_time[x.name.split("(")[0]] += (
                    (x.end - x.start) / 1e9 / len(self.programs))
        idle: Dict[str, float] = defaultdict(float)
        for plane in self.ops:
            busy = self.busy_intervals(plane)
            edges = [self.start] + [x for iv in busy for x in iv] + [self.end]
            gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
            for (s, e), name in zip(gaps, self._host_at(
                    [(s + e) // 2 for s, e in gaps])):
                idle[name] += (e - s) / 1e9 / len(self.ops)
        rank = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
            d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(prog_time), "idle_gaps": rank(idle)}

    def _host_at(self, times: List[int]) -> List[str]:
        """For sorted ``times``: what the host thread was inside, as
        ``<harness span> > <innermost event>``. Events on one thread nest,
        so a stack of the open ones holds the answer."""
        events = sorted(self.host, key=lambda e: (e.start, -e.end))
        out, stack, i = [], [], 0
        for t in times:
            while i < len(events) and events[i].start <= t:
                stack.append(events[i])
                i += 1
            stack = [e for e in stack if e.end > t]
            names = [e.name[len(PREFIX):] for e in stack
                     if e.name.startswith(PREFIX)
                     and e.name != PREFIX + "round"]
            inner = next((e.name for e in reversed(stack)
                          if not e.name.startswith(PREFIX)), None)
            if not names:
                out.append("between_rounds")
            else:
                out.append(names[-1] + (f" > {inner}" if inner else ""))
        return out


def window(trace: Trace, least_bytes_per_round: int, k: int,
           hbm_bytes_per_s: float) -> Optional[TracedWindow]:
    """Cut ``trace`` to its complete rounds; None where it holds none."""
    rounds = [e for e in trace.spans
              if e.name == "round" and e.stat("round", -1) >= 0]
    if not rounds:
        return None
    lo = min(e.start for e in rounds)
    hi = max(e.end for e in rounds)
    inside = lambda e: lo <= e.start and e.end <= hi  # noqa: E731
    return TracedWindow(
        start=lo, end=hi, rounds=len(rounds), n_updates=k * len(rounds),
        spans=[e for e in trace.spans if inside(e)],
        host=[e for e in trace.host if inside(e)],
        programs={p: [e for e in v if lo <= e.start < hi]
                  for p, v in trace.programs.items()},
        ops={p: v for p, v in trace.ops.items()},
        least_bytes=least_bytes_per_round * len(rounds),
        hbm_bytes_per_s=hbm_bytes_per_s)
