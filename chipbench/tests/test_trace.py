"""The reduction from a trace to the per-layer metrics."""
import dataclasses

import pytest

from chipbench import trace as T
from chipbench.plan import REPO, load_reader

DEV = "/device:TPU:0"


def _reader(name):
    return load_reader(REPO / "chipbench" / "metrics" / f"{name}.py")


def test_union_merges_overlaps_and_drops_empty():
    assert T.union([(5, 9), (0, 2), (1, 3), (9, 10), (12, 12)]) == [
        (0, 3), (5, 10)]


def _synthetic():
    """Two rounds of K=2 on one chip, in ns: host spans and the device
    programs and operations they launched (one operation overlaps the
    next, one starts before the window and is clipped)."""
    s = lambda n, a, b, r: T.Event(n, a, b, (("round", r),))  # noqa: E731
    spans = [s("round", 100, 1000, 0), s("publish", 100, 150, 0),
             s("drain", 150, 400, 0), s("finish_round", 400, 500, 0),
             s("wait", 500, 1000, 0),
             s("round", 1000, 2000, 1), s("publish", 1000, 1100, 1),
             s("drain", 1100, 1300, 1), s("finish_round", 1300, 1400, 1),
             s("wait", 1400, 2000, 1),
             s("round", 0, 90, -1)]  # the warm-up round is not counted
    e = lambda n, a, b: T.Event(n, a, b)  # noqa: E731
    programs = [e("jit_pair_fuse", 200, 600), e("jit_divide", 650, 900),
                e("jit_pair_fuse", 1200, 1600), e("jit_before", 50, 120)]
    ops = [e("fusion", 200, 600), e("copy", 550, 700), e("div", 650, 900),
           e("fusion", 1200, 1600), e("early", 50, 120)]
    host = [dataclasses.replace(x, name=T.PREFIX + x.name) for x in spans]
    host += [e("PjitFunction(pair_fuse)", 150, 190)]
    return T.Trace(spans, {DEV: programs}, {DEV: ops}, host)


def test_window_cuts_the_trace_to_its_rounds():
    w = T.window(_synthetic(), least_bytes_per_round=1000, k=2,
                 hbm_bytes_per_s=1e12)
    assert (w.start, w.end, w.rounds, w.n_updates) == (100, 2000, 2, 4)
    assert w.window_s == pytest.approx(1900e-9)
    # busy: [100,120) clipped + [200,900) + [1200,1600)
    assert w.busy_s() == pytest.approx((20 + 700 + 400) * 1e-9)
    assert w.launches() == 3
    assert w.span_s("drain") == pytest.approx(450e-9)
    assert w.least_bytes == 2000
    bd = w.breakdown()
    assert bd["device_ops"] == [["jit_pair_fuse", pytest.approx(800e-9)],
                                ["jit_divide", pytest.approx(250e-9)]]
    # idle gaps [120,200), [900,1200), [1600,2000), by their middles
    assert dict((k, v) for k, v in bd["idle_gaps"]) == {
        "drain > PjitFunction(pair_fuse)": pytest.approx(80e-9),
        "publish": pytest.approx(300e-9), "wait": pytest.approx(400e-9)}


def test_metric_readers_on_the_synthetic_window():
    w = T.window(_synthetic(), 1000, 2, 1e12)
    busy = (20 + 700 + 400) * 1e-9
    assert _reader("device_idle.backlog")(w) == pytest.approx(
        100 * (1 - busy / 1900e-9))
    assert _reader("launches_per_update.backlog")(w) == pytest.approx(3 / 4)
    assert _reader("drain_host_ms_per_update.backlog")(w) == pytest.approx(
        1e3 * 450e-9 / 4)
    assert _reader("drain_host_ms_per_update.stream")(w) == pytest.approx(
        1e3 * 450e-9 / 4)
    assert _reader("device_ms_per_update.stream")(w) == pytest.approx(
        1e3 * busy / 4)
    assert _reader("agg_roofline.backlog")(w) == pytest.approx(
        100 * 2000 / 1e12 / busy)


def test_readers_find_nothing_in_a_trace_without_a_device():
    tr = _synthetic()
    w = T.window(T.Trace(tr.spans, {}, {}), 1000, 2, 1e12)
    for name in ("device_idle.backlog", "launches_per_update.backlog",
                 "device_ms_per_update.stream", "agg_roofline.backlog"):
        assert _reader(name)(w) is None
    assert T.window(T.Trace([], {}, {}), 1000, 2, 1e12) is None


RECORDED = REPO / "chipbench" / "tests" / "data" / "tiny_round.xplane.pb"


def test_recorded_chip_trace():
    """One round recorded on a TPU v5e: FedSGD, K=3, leaves (3,3,16,32),
    (32,) and (256,128). The round launches 30 programs: the first fold 3
    multiplies, two more folds 2 x 4 + 1 each (reshape, reshape, pair_fuse,
    reshape for the 2-D and 4-D leaves; pair_fuse alone for the vector),
    ``result`` 3 divides, the FedSGD ``apply`` 3 multiplies and 3
    subtracts."""
    tr = T.load(RECORDED)
    assert sorted(e.name for e in tr.spans) == [
        "drain", "finish_round", "publish", "publish", "publish", "round",
        "wait"]
    w = T.window(tr, least_bytes_per_round=1000, k=3, hbm_bytes_per_s=819e9)
    assert (w.rounds, w.n_updates, w.launches()) == (1, 3, 30)
    names = [p.name.split("(")[0] for p in w.programs[DEV]]
    assert names.count("jit_pair_fuse") == 6
    assert names.count("jit_reshape") == 12
    # host spans and device events share one clock: every program runs
    # inside the round, after the drain that dispatched the folds began,
    # and the programs of finish_round after it began
    span = {e.name: e for e in w.spans}
    for p in w.programs[DEV]:
        assert span["drain"].start <= p.start and p.end <= span["wait"].end
        if p.name.startswith(("jit_true_divide", "jit_subtract")):
            assert p.start >= span["finish_round"].start
    assert 0 < w.busy_s() < w.window_s
    idle = _reader("device_idle.backlog")(w)
    assert 0 < idle < 100
    assert w.busy_s() == pytest.approx(sum(
        e - s for s, e in w.busy_intervals(DEV)) / 1e9)
    bd = w.breakdown()
    assert {"jit_pair_fuse", "jit_reshape"} <= {n for n, _ in bd["device_ops"]}
    assert sum(v for _, v in bd["idle_gaps"]) == pytest.approx(
        w.window_s - w.busy_s())
    assert any(n.startswith("drain > PjitFunction(")
               for n, _ in bd["idle_gaps"])
