"""The aggregator's host spans on the profiler's clock (``repro.obs.span``)
and the benchmark's readers of them (``chipbench/metrics/``).

A round served by the benchmark's loop on the CPU, traced by
``jax.profiler`` and read back through ``chipbench.trace``, holds the
program's three spans inside the harness's; the five readers are checked
on a hand-built window, to the nanosecond.
"""
import dataclasses
import subprocess
import sys

import jax
import pytest

from chipbench import loop, traffic
from chipbench import trace as T
from chipbench.plan import REPO, load_reader

K = 3
#: leaf shapes: one vector; then a conv, a bias and a dense layer
LEAVES = {
    "one_leaf": [["b", [16]]],
    "three_leaves": [["conv.w", [3, 3, 4, 8]], ["conv.b", [8]],
                     ["fc.w", [40, 24]]],
}
MIXES = {
    "backlog": {"parties_per_round": K, "arrivals": "closed",
                "drain": "round", "n_examples": [1, 9]},
    "stream": {"parties_per_round": K, "arrivals": "closed",
               "drain": "arrival", "n_examples": [1, 9]},
}


def _reader(name):
    return load_reader(REPO / "chipbench" / "metrics" / f"{name}.py")


def _served_trace(tmp_path, leaves, mix, seconds=0.2):
    config = {"leaves": leaves, "dtype": "float32", "algorithm": "fedsgd",
              "server_lr": 1.0}
    tr = traffic.make(mix, 2**33 + 5, seconds)
    updates, global0 = loop.make_inputs(config, K, 2**33 + 5)
    loop.warm_up(config, tr, updates, global0)
    win = loop.run_window(config, tr, updates, global0, seconds,
                          trace_dir=str(tmp_path), trace_seconds=seconds)
    path = T.find_xplane(tmp_path)
    return win, path, T.window(T.load(path), 0, K, 1.0)


def _inside(e, outer):
    return outer.start <= e.start and e.end <= outer.end


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("leaves", sorted(LEAVES))
def test_program_spans_nest_in_the_harness_spans(tmp_path, leaves, mix):
    win, path, w = _served_trace(tmp_path, LEAVES[leaves], MIXES[mix])
    assert w is not None and w.rounds >= 1
    assert w.rounds <= len(win.rounds)
    named = lambda n: [e for e in w.host if e.name == n]  # noqa: E731
    drains, folds = named("repro.drain"), named("repro.fold")
    finishes = named("repro.finish_round")
    outer_drains = named("chipbench.drain")
    outer_finishes = named("chipbench.finish_round")
    # one repro.drain per drain call, one fold per update, whatever the
    # number of leaves; one finish_round per round
    drains_a_round = K if mix == "stream" else 1
    assert len(outer_drains) == len(drains) == drains_a_round * w.rounds
    assert len(folds) == K * w.rounds
    assert len(outer_finishes) == len(finishes) == w.rounds
    for d in drains:
        assert sum(_inside(d, o) for o in outer_drains) == 1
    for f in folds:
        assert sum(_inside(f, d) for d in drains) == 1
    for f in finishes:
        assert sum(_inside(f, o) for o in outer_finishes) == 1
    # the runtime's dispatches nest in the folds
    runtime = [e for e in w.host
               if not e.name.startswith(("repro.", "chipbench."))]
    assert any(_inside(x, f) for f in folds for x in runtime)
    # the readers find them
    for name in ("fold_host_ms_per_update.backlog",
                 "fold_host_ms_per_update.stream",
                 "fold_self_ms_per_update.backlog",
                 "finish_host_ms_per_round.backlog",
                 "finish_host_ms_per_round.stream"):
        assert _reader(name)(w) > 0
    assert (_reader("fold_self_ms_per_update.backlog")(w)
            < _reader("fold_host_ms_per_update.backlog")(w))
    assert (1e3 * sum(f.end - f.start for f in folds) / 1e9
            <= 1e3 * w.span_s("drain"))


def test_program_spans_carry_their_round(tmp_path):
    """``repro.drain`` and ``repro.finish_round`` name the round of the
    harness's round span around them."""
    _, path, _ = _served_trace(tmp_path, LEAVES["one_leaf"], MIXES["stream"])
    events = [e for plane in jax.profiler.ProfileData.from_file(
        str(path)).planes for line in plane.lines for e in line.events]
    rounds = [e for e in events if e.name == "chipbench.round"]
    ours = [e for e in events
            if e.name in ("repro.drain", "repro.finish_round")]
    assert ours
    for e in ours:
        outer = [r for r in rounds if r.start_ns <= e.start_ns
                 and e.start_ns + e.duration_ns
                 <= r.start_ns + r.duration_ns]
        assert len(outer) == 1
        assert dict(e.stats)["round"] == dict(outer[0].stats)["round"]


def test_span_is_exported_from_repro_obs():
    from repro.obs import span
    from repro.obs.spans import span as direct

    assert span is direct
    assert isinstance(span("fold"), jax.profiler.TraceAnnotation)
    with span("drain", round=3):  # no trace open: nothing recorded
        pass


def test_control_plane_imports_obs_without_jax():
    code = ("import sys, repro.obs, repro.core; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=REPO, env={"PYTHONPATH": str(REPO / "src")})


# ---- the readers on a hand-built window ----------------------------------

def _window(with_program=True):
    """Two rounds of K=2, in ns, one drain a round. Runtime events nest in
    the folds, overlapping one another in fold 1; one fold has none."""
    e = lambda n, a, b: T.Event(n, a, b)  # noqa: E731
    spans = [T.Event("round", 0, 1000, (("round", 0),)),
             e("drain", 0, 400), e("finish_round", 400, 600),
             e("wait", 600, 1000),
             T.Event("round", 1000, 2000, (("round", 1),)),
             e("drain", 1000, 1500), e("finish_round", 1500, 1800),
             e("wait", 1800, 2000)]
    host = [dataclasses.replace(x, name=T.PREFIX + x.name) for x in spans]
    runtime = [
        # fold 0 [10, 110): two disjoint dispatches, 30 ns
        e("PjitFunction(reshape)", 20, 40), e("DevicePut", 60, 70),
        # fold 1 [120, 380): overlapping [130,200) [150,260) [150,160),
        # and [300,310): union 130 + 10 = 140 ns
        e("PjitFunction(pair_fuse)", 130, 200), e("DevicePut", 150, 260),
        e("ParseArguments", 150, 160), e("PjitFunction(reshape)", 300, 310),
        # fold 3 [1210, 1460): one dispatch to its very end, 60 ns
        e("PjitFunction(pair_fuse)", 1400, 1460),
        # finish_round's own dispatch, outside every fold
        e("PjitFunction(true_divide)", 420, 500),
    ]
    program = [e("repro.drain", 5, 395), e("repro.fold", 10, 110),
               e("repro.fold", 120, 380), e("repro.finish_round", 405, 595),
               e("repro.drain", 1005, 1495), e("repro.fold", 1010, 1200),
               e("repro.fold", 1210, 1460),
               e("repro.finish_round", 1505, 1705)]
    host += runtime + (program if with_program else [])
    return T.window(T.Trace(spans, {}, {}, host), 0, 2, 1.0)


FOLD_NS = 100 + 260 + 190 + 250
SELF_NS = (100 - 30) + (260 - 140) + 190 + (250 - 60)
FINISH_NS = 190 + 200
READINGS = {
    "fold_host_ms_per_update.backlog": FOLD_NS / 1e6 / 4,
    "fold_host_ms_per_update.stream": FOLD_NS / 1e6 / 4,
    "fold_self_ms_per_update.backlog": SELF_NS / 1e6 / 4,
    "finish_host_ms_per_round.backlog": FINISH_NS / 1e6 / 2,
    "finish_host_ms_per_round.stream": FINISH_NS / 1e6 / 2,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader_on_a_hand_built_window(name):
    w = _window()
    assert (w.rounds, w.n_updates) == (2, 4)
    assert _reader(name)(w) == pytest.approx(READINGS[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader_finds_nothing_without_program_spans(name):
    """A program older than its spans: the trace holds the harness's spans
    and the runtime's events only."""
    w = _window(with_program=False)
    assert w.rounds == 2
    assert _reader(name)(w) is None


def test_idle_gaps_take_the_program_span_they_fall_in():
    """``breakdown`` names a gap by the innermost non-harness event open at
    its middle: a program span where no runtime event is open."""
    w = _window()
    plane = "/device:TPU:0"
    # the device busy everywhere but around 90, 165, 340, 550 and 1300
    busy = [(0, 80), (100, 160), (170, 330), (350, 540), (560, 1290),
            (1310, 2000)]
    w.ops = {plane: [T.Event("op", a, b) for a, b in busy]}
    gaps = dict((k, v) for k, v in w.breakdown()["idle_gaps"])
    assert gaps == {
        # [80,100), [330,350), [1290,1310): inside a fold, between dispatches
        "drain > repro.fold": pytest.approx(60e-9),
        # [160,170): a dispatch inside a fold names the gap, as before
        "drain > DevicePut": pytest.approx(10e-9),
        # [540,560): finish_round's own Python
        "finish_round > repro.finish_round": pytest.approx(20e-9),
    }
