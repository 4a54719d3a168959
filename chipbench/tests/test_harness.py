"""The harness end to end on the CPU, at small shapes: each mix's loop, the
check that decides ``correct`` against the faults it must catch and
against the control, the refusal to run without a TPU, and a cell and a
metric added as files alone."""
import dataclasses
import json
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control, loop, plan, roofline, traffic
from chipbench import trace as trace_mod
from chipbench.plan import REPO
from chipbench.run import measure, report

LEAVES = [["conv.w", [3, 3, 4, 8]], ["conv.b", [8]], ["fc.w", [40, 24]]]
E2E = [plan.Metric("updates_per_s", "updates/s"),
       plan.Metric("round_latency_p50_ms", "ms"),
       plan.Metric("setup_s", "s")]


def _json(*parts):
    return json.loads(REPO.joinpath("chipbench", *parts).read_text())


def tiny_cell(config_name, traffic_name, **mix):
    """The named configuration (its algorithm, dtype and limits) at small
    leaf shapes, under the named mix with K=3."""
    cfg = dict(_json("configs", f"{config_name}.json"), leaves=LEAVES)
    m = dict(_json("mixes", f"{traffic_name}.json"), parties_per_round=3,
             **mix)
    return plan.Cell(f"{config_name}.{traffic_name}", 1, cfg, m, E2E, [])


CELLS = {
    "vgg16.backlog": lambda: tiny_cell("vgg16", "backlog"),
    "effnetb7.backlog": lambda: tiny_cell("effnetb7", "backlog"),
    "vgg16.stream": lambda: tiny_cell("vgg16", "poisson_19.2",
                                      rate_updates_per_s=24.0),
}


def _run(cell, seconds=0.5, **kw):
    res = measure(cell, 2**33 + 7, seconds, **kw)
    return res, report(cell, res, kw.get("trace", False),
                       cell.config["limits"])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_mix_loop_serves_correct_rounds(name):
    cell = CELLS[name]()
    res, out = _run(cell)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] == len(res["win"].rounds) >= 3
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"updates_per_s", "round_latency_p50_ms",
                                   "setup_s"}
    assert all(r.drained == 3 and r.published == 1 for r in res["win"].rounds)
    # a seeded sample besides the last round was compared
    assert len(res["gaps"]) == min(3, len(res["win"].rounds))
    assert list(out)[-1] == "checks"


def test_stream_rounds_are_timed_from_their_last_due_update():
    cell = CELLS["vgg16.stream"]()
    res, _ = _run(cell, seconds=1.0)
    tr = traffic.make(cell.mix, 2**33 + 7, 1.0)
    win = res["win"]
    assert len(win.rounds) == tr.n_rounds
    for r in win.rounds:
        assert r.due == pytest.approx(win.t0 + tr.due[3 * r.round_idx + 2])
        assert r.ready >= r.due
    assert len(win.lateness) == 3 * tr.n_rounds


def test_traffic_gives_every_seed_the_same_work():
    mix = dict(_json("mixes", "poisson_19.2.json"), rate_updates_per_s=90.0)
    a, b = (traffic.make(mix, s, 30.0) for s in (2**40 + 1, 5))
    assert a.n_rounds == b.n_rounds == int(90 * 30) // 10
    # one arrival schedule for every seed: the exponential's quantiles
    gaps = np.sort(np.diff(a.due, prepend=0))
    n = len(gaps)
    assert np.allclose(gaps, -np.log1p(-(np.arange(n) + 0.5) / n) / 90.0)
    assert np.array_equal(a.due, b.due)
    assert not np.array_equal(np.diff(a.due, prepend=0), gaps)
    # the seed draws which party's update arrives when, and its weight
    assert not np.array_equal(a.round(4).order, b.round(4).order)
    assert not np.array_equal(a.round(4).n_examples, b.round(4).n_examples)
    again = traffic.make(mix, 2**40 + 1, 30.0)
    assert np.array_equal(a.due, again.due)
    assert np.array_equal(a.round(4).order, again.round(4).order)
    assert sorted(a.round(4).order) == list(range(10))


def _fold_returns_state_unchanged(monkeypatch):
    import repro.fl.fusion as fusion

    real = fusion.accumulate
    monkeypatch.setattr(fusion, "accumulate", lambda acc, u, w, **kw: (
        real(acc, u, w, **kw) if acc is None else acc))


def _half_the_batch_left_out(monkeypatch):
    import itertools

    from repro.fl.fusion import FusionState

    real, calls = FusionState.fold, itertools.count()
    monkeypatch.setattr(FusionState, "fold", lambda self, u, w: (
        real(self, u, w) if next(calls) % 2 == 0 else self))


def _answer_altered_where_produced(monkeypatch):
    from repro.core.queue import MessageQueue

    real = MessageQueue.publish_fused

    def publish(self, job_id, round_idx, model, timestamp=None):
        leaf = model[2]
        bump = 1e-3 * jnp.max(jnp.abs(leaf))
        model = [*model[:2], leaf.at[0, 0].add(bump)]
        return real(self, job_id, round_idx, model, timestamp)

    monkeypatch.setattr(MessageQueue, "publish_fused", publish)


@pytest.mark.parametrize("cell_name", ["vgg16.backlog", "effnetb7.backlog",
                                       "vgg16.stream"])
@pytest.mark.parametrize("fault", [_fold_returns_state_unchanged,
                                   _half_the_batch_left_out,
                                   _answer_altered_where_produced])
def test_check_refuses_a_broken_timed_path(monkeypatch, fault, cell_name):
    """Each fault a one-chip cell can have (there is no exchange between
    chips to leave out), planted under the window, turns ``correct``
    false."""
    fault(monkeypatch)
    _, out = _run(CELLS[cell_name](), seconds=0.3)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell_name", sorted(CELLS))
def test_control_in_bfloat16_is_not_correct(cell_name):
    cell = CELLS[cell_name]()
    res, out = _run(cell, seconds=0.3,
                    executor_of=control.executor_of(cell.config))
    assert out["correct"] is False
    assert out["checks"]["model_gap"]["value"] > \
        3 * cell.config["limits"]["model_gap"]
    assert out["checks"]["bad_rounds"]["value"] == 0
    # the same reference in float32, in the same place, passes
    res, out = _run(cell, seconds=0.3, executor_of=control.executor_of(
        cell.config, jnp.float32))
    assert out["correct"] is True, out["checks"]


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return proc.returncode != 0 and not (lines and lines[-1].startswith("{"))


def test_run_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "vgg16.backlog",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert _no_result(proc), proc.stdout[-2000:]
    assert "needs 1 TPU" in proc.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "vgg16.backlog",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": ""})
    assert _no_result(proc)


def test_a_cell_and_a_metric_added_as_files_alone(tmp_path):
    """A new configuration, mix, per-layer metric and cell need only new
    files and new entries in BENCHMARK.json."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    base = tmp_path / "chipbench"
    cfg = dict(_json("configs", "vgg16.json"), name="tiny", leaves=LEAVES)
    (base / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (base / "mixes" / "backlog_k3.json").write_text(json.dumps(
        dict(_json("mixes", "backlog.json"), parties_per_round=3)))
    (base / "metrics" / "traced_rounds.dummy.py").write_text(
        "def read(w):\n    return float(w.rounds) if w.rounds else None\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "a test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny.backlog_k3", "config": "tiny",
                               "traffic": "backlog_k3", "chips": 1,
                               "why": "a test"})
    bench["end_to_end"][0]["workloads"].append("tiny.backlog_k3")
    bench["per_layer"].append({"name": "traced_rounds.dummy", "unit": "rounds",
                               "better": "higher", "source": "program_span",
                               "layer": "test", "moves": "updates_per_s",
                               "workloads": ["tiny.backlog_k3"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = plan.load_cell("tiny.backlog_k3", repo=tmp_path)
    assert cell.config["name"] == "tiny" and cell.mix["parties_per_round"] == 3
    assert [m.name for m in cell.per_layer] == ["traced_rounds.dummy"]
    assert [m.name for m in cell.end_to_end] == ["updates_per_s", "setup_s"]
    res, out = _run(cell, seconds=0.3, trace=True)
    assert out["correct"] is True
    assert out["metrics"] == {"traced_rounds.dummy": {
        "value": float(res["traced"].rounds), "unit": "rounds"}}
    _, out = _run(cell, seconds=0.3)
    assert set(out["metrics"]) == {"updates_per_s", "setup_s"}


@pytest.mark.parametrize("config_name", ["vgg16", "effnetb7"])
def test_roofline_counts_no_more_than_a_one_sweep_round(config_name):
    """A round done in one sweep (one fused program reading each update and
    the global model once and writing the new model once) moves at least
    the least bytes, so at the chip's peak bandwidth it reads at most 100%
    of the roofline; the count does not depend on the kernels."""
    cfg = dict(_json("configs", f"{config_name}.json"), leaves=LEAVES)
    k = 10
    shapes = [jax.ShapeDtypeStruct(tuple(s), jnp.float32) for _, s in LEAVES]

    def one_sweep(updates, w, prev):
        out = []
        for i, g in enumerate(prev):
            acc = sum(u[i] * w[j] for j, u in enumerate(updates))
            mean = acc / jnp.sum(w)
            out.append(g - mean if cfg["algorithm"] == "fedsgd" else mean)
        return out

    cost = jax.jit(one_sweep).lower(
        tuple(shapes for _ in range(k)), jax.ShapeDtypeStruct((k,), jnp.float32),
        shapes).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    moved = cost["bytes accessed"]
    least = roofline.least_bytes_per_round(cfg, k)
    assert least <= moved <= least * 1.01
    reader = plan.load_reader(REPO / "chipbench" / "metrics"
                              / "agg_roofline.backlog.py")
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    busy_ns = int(np.ceil(moved / peak * 1e9))
    ops = {"/device:TPU:0": [trace_mod.Event("fusion", 0, busy_ns)]}
    w = trace_mod.TracedWindow(0, busy_ns, 1, k, [], {}, ops, least, peak)
    assert 99.0 <= reader(w) <= 100.0
